"""kernels/ssd.py: the chunked state-space (Mamba-2 "SSD") operator against
the recurrence run token by token, forward and every gradient, at two
chunk sizes (the result does not depend on the chunk); the two Mosaic
kernels through the Pallas interpreter against the plain route at ONE
small shape (a CPU test's seconds follow the programs it compiles); and
the choice between the routes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import ssd

OPERANDS = ("x", "dt", "A", "B", "C", "D")


def recurrence(x, dt, A, Bm, Cm, D):
    """S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T; y_t = S_t^T C_t + D
    x_t, a token at a time. x [B, T, H, P], dt [B, T, H], A, D [H], Bm,
    Cm [B, T, N]."""
    B, T, H, P = x.shape

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        S = (S * jnp.exp(dt_t * A)[..., None, None]
             + jnp.einsum("bn,bhp->bhnp", b_t, x_t * dt_t[..., None]))
        return S, jnp.einsum("bhnp,bn->bhp", S, c_t) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((B, H, Bm.shape[-1], P)),
                        tuple(jnp.moveaxis(v, 1, 0)
                              for v in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def _operands(B, T, H, P, N, seed):
    """dt in (0.02, 0.7) and A in (-15, -1): a head forgets within a few
    tokens or remembers across every chunk of the sequence."""
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (B, T, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0., maxval=2.7)),
            jax.random.normal(ks[3], (B, T, N)) * 0.5,
            jax.random.normal(ks[4], (B, T, N)) * 0.5,
            jax.random.normal(ks[5], (H,)),
            jax.random.normal(ks[6], (B, T, H, P)))


def _out_and_grads(op, args, w):
    """y and the gradient of sum(y * w) by each operand, one program."""
    def run(*a):
        y, vjp = jax.vjp(op, *a)
        return (y,) + vjp(w)
    return dict(zip(("y",) + OPERANDS, jax.jit(run)(*args)))


def _chunked(chunk, fused=False):
    def op(x, dt, A, Bm, Cm, D):
        G = ssd.chunk_cumsum(dt, A, chunk)
        if fused:
            return ssd._fused(x, dt, G, Bm, Cm, D, chunk, True)
        return ssd.ssd_chunk_scan(x, dt, G, Bm, Cm, D, chunk=chunk)
    return op


@functools.lru_cache(maxsize=None)
def _plain_case(chunk):
    """T = 40: five chunks of 8, or two of 16 and a ragged third."""
    *args, w = _operands(2, 40, 4, 8, 16, 0)
    return (_out_and_grads(_chunked(chunk), args, w),
            _out_and_grads(recurrence, args, w))


@pytest.mark.parametrize("what", ("y",) + OPERANDS)
@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_form_matches_the_recurrence(chunk, what):
    """float32 on both sides: 1e-5 of the largest value is rounding (the
    chunked form sums in another order), and a wrong decay, mask or state
    is tenths."""
    got, want = _plain_case(chunk)
    want = np.asarray(want[what])
    np.testing.assert_allclose(np.asarray(got[what]), want,
                               atol=1e-5 * np.abs(want).max())


@functools.lru_cache(maxsize=None)
def _kernel_case():
    """Eight heads of 64 (two a tile of lanes), a state of 128, two
    chunks of 32 tokens, a block of L 16 rows: the blocks on and under
    the diagonal, the pairs of heads, the state between chunks."""
    *args, w = _operands(1, 64, 8, 64, 128, 1)
    was = ssd._SUB
    ssd._SUB = 16
    try:
        got = _out_and_grads(_chunked(32, fused=True), args, w)
    finally:
        ssd._SUB = was
    return got, _out_and_grads(_chunked(32), args, w)


@pytest.mark.parametrize("what", ("y",) + OPERANDS)
def test_kernels_match_the_plain_route(what):
    got, want = _kernel_case()
    assert got[what].shape == want[what].shape
    assert got[what].dtype == want[what].dtype
    want = np.asarray(want[what])
    np.testing.assert_allclose(np.asarray(got[what]), want,
                               atol=2e-5 * np.abs(want).max())


def test_the_route_is_chosen_from_the_platform_and_the_shapes(monkeypatch):
    """On a TPU heads of 64 or 128 channels, eight a grid step, with a
    state and a chunk of whole lane tiles go through the kernels; the
    tiny configuration's 16 take the plain route there too, as everything
    does on the CPU."""
    taken = []
    monkeypatch.setattr(
        ssd, "_fused", lambda *a: taken.append("kernels") or ssd._plain(*a[:7]))
    for on_tpu, (H, P, N, Q), want in (
            (True, (8, 64, 128, 128), ["kernels"]),
            (True, (8, 128, 128, 256), ["kernels"]),
            (True, (4, 16, 16, 8), []), (True, (8, 64, 128, 64), []),
            (True, (4, 64, 128, 128), []), (False, (8, 64, 128, 128), [])):
        monkeypatch.setattr(ssd, "_on_tpu", lambda on_tpu=on_tpu: on_tpu)
        del taken[:]
        dt = jnp.full((1, Q, H), 0.1)
        y = ssd.ssd_chunk_scan(
            jnp.ones((1, Q, H, P)), dt, ssd.chunk_cumsum(dt, -jnp.ones(H), Q),
            jnp.ones((1, Q, N)), jnp.ones((1, Q, N)), jnp.ones(H), chunk=Q)
        assert taken == want, (H, P, N, Q)
        assert y.shape == (1, Q, H, P)
