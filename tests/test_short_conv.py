"""kernels/short_conv.py: the two Mosaic kernels (short causal convolution,
SiLU, the heads' L2 norm; forward and backward), through the Pallas
interpreter, against the module's plain `jax.numpy` route, and the choice
between the routes. The plain route itself is held to
`tests/reference/solar_open2.py` by `tests/test_solar_open2.py`. The
state-space layer's form of the same kernels (a bias, no norm, the
outputs by widths) likewise; its plain route is held to
`chipbench/reference_granitemoehybrid.py` by `tests/test_granite_hybrid.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import short_conv as sc

HEADS, D = 2, 128
BLOCK = sc._block_rows(10 ** 6, 3 * HEADS * D, 4)


def _out_and_grads(fn, pre, w):
    """q, k, v and the cotangents of `pre` and `w` through each of them."""
    def through(i):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(
            3.0 * fn(*a)[i].astype(jnp.float32))), argnums=(0, 1))
    return tuple(fn(pre, w)) + sum((through(i)(pre, w) for i in range(3)), ())


@pytest.mark.parametrize("dtype,tol,T,taps,B", [
    (jnp.float32, 1e-5, 2 * BLOCK + 5, 4, 1),   # a border inside the taps' reach
    (jnp.float32, 1e-5, BLOCK + 70, 2, 2),      # ragged, two taps, no leak over B
    (jnp.float32, 1e-5, 37, 4, 2),              # shorter than a block
    (jnp.bfloat16, 2 ** -7, 2 * BLOCK + 5, 4, 1),
    (jnp.bfloat16, 2 ** -7, 37, 2, 2)])
def test_conv_kernels_match_the_plain_route(dtype, tol, T, taps, B):
    """Values and all three pairs of gradients, in the operands' own
    dtypes. Every length leaves the last block ragged: its rows past T
    must neither reach dpre's last rows nor dw."""
    ks = jax.random.split(jax.random.key(T + taps), 2)
    pre = jax.random.normal(ks[0], (B, T, 3 * HEADS * D)).astype(dtype)
    w = jax.random.uniform(ks[1], (taps, 3 * HEADS * D), minval=-0.5,
                           maxval=0.5).astype(dtype)
    got = _out_and_grads(lambda p, w_: sc._fused(p, w_, HEADS, True), pre, w)
    want = _out_and_grads(jax.jit(lambda p, w_: sc._plain(p, w_, HEADS)),
                          pre, w)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=tol * np.abs(b).max())


def test_the_route_is_chosen_from_the_platform_and_the_head_width(monkeypatch):
    """On a TPU a head of 128 channels goes through the kernels; one of 8
    (`solar_open2_tiny`) takes the plain route there too, as everything
    does on the CPU."""
    taken = []
    monkeypatch.setattr(sc, "_fused",
                        lambda *a: taken.append("kernels") or sc._plain(*a[:3]))
    for on_tpu, d, want in ((True, 8, []), (True, 128, ["kernels"]),
                            (False, 128, [])):
        monkeypatch.setattr(sc, "_on_tpu", lambda on_tpu=on_tpu: on_tpu)
        del taken[:]
        q, k, v = sc.conv_silu_l2norm(jnp.ones((1, 5, 3 * 2 * d)),
                                      jnp.ones((4, 3 * 2 * d)), 2)
        assert taken == want
        assert q.shape == k.shape == v.shape == (1, 5, 2, d)
        np.testing.assert_allclose(np.asarray(jnp.sum(q * q, -1)), 1.0,
                                   rtol=1e-4)


# -- the state-space layer's form: conv + bias + SiLU, outputs by widths -------

WIDTHS = (256, 128, 128)


def _bias_out_and_grads(fn, pre, w, b):
    """The outputs and the cotangents of `pre`, `w` and the bias, one
    program."""
    def run(*a):
        outs, vjp = jax.vjp(fn, *a)
        return tuple(outs) + vjp(tuple(
            jnp.cos(3.0 * o.astype(jnp.float32)).astype(o.dtype)
            for o in outs))
    return jax.jit(run)(pre, w, b)


@pytest.mark.parametrize("dtype,tol,T", [
    (jnp.float32, 1e-5, 2 * sc._block_rows(10 ** 6, sum(WIDTHS), 4) + 5),
    (jnp.bfloat16, 2 ** -7, 37)])
def test_bias_form_of_the_kernels_matches_the_plain_route(dtype, tol, T):
    """x | B | C side by side, a bias a channel, no norm: the values of
    each part and the gradients of `pre`, the taps and the bias."""
    C = sum(WIDTHS)
    ks = jax.random.split(jax.random.key(T), 3)
    pre = jax.random.normal(ks[0], (2, T, C)).astype(dtype)
    w = jax.random.uniform(ks[1], (4, C), minval=-0.5,
                           maxval=0.5).astype(dtype)
    b = (0.3 * jax.random.normal(ks[2], (C,))).astype(dtype)
    got = _bias_out_and_grads(
        lambda *a: sc._fused_bias(*a, WIDTHS, True), pre, w, b)
    want = _bias_out_and_grads(
        lambda *a: sc.conv_bias_silu(*a, WIDTHS), pre, w, b)
    assert [x.shape[-1] for x in got[:3]] == list(WIDTHS)
    for a, e in zip(got, want):
        assert a.shape == e.shape and a.dtype == e.dtype
        e = np.asarray(e, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), e,
                                   atol=tol * np.abs(e).max())


def test_the_bias_moves_the_output_by_silu_of_it():
    """Zero input: every channel reads silu(bias), at every token."""
    b = jnp.linspace(-2.0, 2.0, 8)
    (out,) = sc.conv_bias_silu(jnp.zeros((1, 5, 8)), jnp.ones((4, 8)), b, (8,))
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(jax.nn.silu(b), (1, 5, 8)),
                               rtol=1e-6)
