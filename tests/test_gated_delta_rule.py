"""kernels/gated_delta_rule.py: the chunked gated delta rule against the
token-by-token recurrence of `tests/reference/solar_open2.py`, on both
routes: plain `jax.numpy` (what the CPU and untiled head widths run) and
the two Mosaic kernels through the Pallas interpreter, at the head width
they tile (dk = dv = 128).

What a case costs here is its programs' compile time, not its length: the
`jax.numpy` route and the recurrence run under `jax.jit` (one program a
pass, not one an operation), and interpret-mode cases that can share a
shape do, so that `_fused_fwd` / `_fused_bwd` are traced once for both."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu.kernels import gated_delta_rule as gdr  # noqa: E402
from paddle_tpu.kernels.gated_delta_rule import chunk_gated_delta_rule  # noqa: E402
from reference import solar_open2 as ref  # noqa: E402


def _qkvgb(T, H=3, dk=16, dv=16, strong=False, batch=None, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    lead = (T,) if batch is None else (batch, T)
    q = ref._l2norm(jax.random.normal(ks[0], lead + (H, dk)))
    k = ref._l2norm(jax.random.normal(ks[1], lead + (H, dk)))
    v = jax.random.normal(ks[2], lead + (H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], lead + (H, dk), minval=-6,
                                    maxval=3.0 if strong else 0.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], lead + (H,)))
    return q, k, v, g, beta


def _out_and_grads(fn, args):
    """fn's output and the cotangent of every operand. The `jax.numpy`
    route and the recurrence come here under `jax.jit`: a program a pass,
    where run eagerly they are one an operation."""
    return (fn(*args),) + jax.grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))),
        argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("T,chunk,strong", [
    (37, 16, False), (64, 32, False), (150, 32, True), (96, 64, False)])
def test_chunked_delta_rule_matches_the_recurrence(T, chunk, strong):
    """Forward and backward at lengths the chunk does not divide, and
    under a decay down to exp(-20) a token: exp(G_i - G_j) split
    carelessly overflows there."""
    args = _qkvgb(T, strong=strong)

    def chunked(*a):
        return chunk_gated_delta_rule(*(x[None] for x in a), chunk=chunk)[0]

    got = _out_and_grads(jax.jit(chunked), args)
    want = _out_and_grads(jax.jit(ref.delta_rule_recurrence), args)
    assert bool(jnp.isfinite(got[0]).all())
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-5)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * float(jnp.abs(b).max()))


def _through_the_kernels(*a, chunk):
    """The chip's route (one Pallas kernel a pass), here through the
    interpreter."""
    return gdr._fused(*a, chunk, 1.0 / np.sqrt(a[0].shape[-1]), True)


def _cast(args, dtype):
    return tuple(x.astype(dtype) for x in args[:3]) + tuple(args[3:])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_chunk_state_kernels_walk_the_chunks_as_the_scan_does(dtype, tol):
    """The kernel route against the `jax.numpy` route on the same inputs,
    at the head width the kernels tile (128): the output and the
    cotangent of every operand, in the operands' own dtypes."""
    args = _cast(_qkvgb(70, H=2, dk=128, dv=128, batch=1, seed=1), dtype)

    for got, want in zip(
            _out_and_grads(functools.partial(_through_the_kernels, chunk=16),
                           args),
            _out_and_grads(jax.jit(functools.partial(
                chunk_gated_delta_rule, chunk=16)), args)):
        assert got.shape == want.shape and got.dtype == want.dtype
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=tol * np.abs(want).max())


@pytest.mark.parametrize("T,H,chunk,strong,dtype,tol", [
    (70, 2, 16, False, jnp.float32, 2e-5),
    (150, 1, 64, True, jnp.float32, 5e-5),
    (100, 2, 32, False, jnp.bfloat16, 1e-2),
    (70, 2, 16, True, jnp.bfloat16, 1e-2)])
def test_chunked_delta_rule_through_the_kernels_matches_the_recurrence(
        T, H, chunk, strong, dtype, tol):
    """The fused forward and backward kernels against the token-by-token
    recurrence, output and all five gradients. Every case: a length the
    chunk does not divide. By case: (1) float32, weak decay, a chunk of
    one sub-block, two heads a grid step; (2) the strong decay (down to
    exp(-20) a token) in float32 and a chunk of FOUR sub-blocks, where
    the inverse's doubling loop turns (one head: the body is unrolled a
    head, and a head is what that loop is written over); (3) bf16
    operands with sub-blocks left of the diagonal and a second head's
    rows in the stacked inverse (chunk of two); (4) bf16 under the strong
    decay. (1) and (4) run the programs the case above compiled."""
    args = _qkvgb(T, H=H, dk=128, dv=128, strong=strong, seed=2)

    def chunked(*a):
        return _through_the_kernels(
            *(x[None] for x in _cast(a, dtype)), chunk=chunk)[0]

    got = _out_and_grads(chunked, args)
    assert got[0].dtype == dtype and bool(jnp.isfinite(got[0]).all())
    want = _out_and_grads(jax.jit(ref.delta_rule_recurrence), args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol * float(jnp.abs(b).max()))


def test_gated_delta_rule_is_public_and_batched():
    q, k, v, g, beta = _qkvgb(40, batch=2, seed=4)
    o = F.gated_delta_rule(*(paddle.to_tensor(np.asarray(t))
                             for t in (q, k, v, g, beta)), chunk=16)
    for b in range(2):
        want = ref.delta_rule_recurrence(q[b], k[b], v[b], g[b], beta[b])
        np.testing.assert_allclose(np.asarray(o.data[b]), np.asarray(want),
                                   atol=1e-5)
