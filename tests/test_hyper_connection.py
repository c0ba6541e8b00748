"""`kernels/hyper_connection.py` (ISSUE 48): the maps against the plain
reference's (`chipbench/reference_xing4_0.py`) and what Sinkhorn's
iterations reach; the three `custom_vjp`s against their `jax.numpy` form
under autodiff, forward and backward; the two Pallas kernels through the
interpreter against the same; the two forms of the residual piece
(`pieces.Residual`, `pieces.HyperConnection`) around one branch."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import hyper_connection as hc
from paddle_tpu.models import pieces
from paddle_tpu.models.xing4_0 import xing4_0_tiny

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import reference_xing4_0 as ref  # noqa: E402

BB, N, T, C = 2, 4, 24, 128
HOW = dict(eps=1e-6, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0))


def arrays(seed=0, dtype=jnp.float32, spread=1.0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.float32)
    X = f(N, BB, T, C).astype(dtype)
    phi = (f(N * C, hc.map_width(N)) * 0.05).astype(dtype)
    scale = jnp.asarray([0.7, 0.5, spread], jnp.float32)
    bias = f(hc.map_width(N)) * 0.5
    return X, phi, scale, bias


def ref_arch(iters=20):
    return ref.Arch(*([0] * 12), **{k: 0 for k in ref.Arch._fields[12:18]},
                    streams=N, hc_iters=iters, hc_eps=1e-6,
                    clamp=(-30.0, 30.0))._replace(eps=1e-6)


def reference_maps(X, phi, scale, bias, iters=20):
    with jax.default_matmul_precision("highest"):
        return ref.hc_maps({"phi": phi, "scale": scale, "bias": bias},
                           jnp.swapaxes(X, 0, 1), ref_arch(iters))


@pytest.mark.parametrize("spread", [0.5, 3.0])
def test_maps_are_the_references_and_h_res_is_doubly_stochastic(spread):
    """H_pre, H_post and H_res equal the reference's to float32 rounding;
    H_res's column sums are within 1e-5 of 1 (the last normalisation is the
    columns'), its row sums nearer 1 after 20 iterations than after 1 by an
    order of magnitude or more (how near depends on H~_res's spread)."""
    X, phi, scale, bias = arrays(1, spread=spread)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: hc.maps(*a, **HOW))(X, phi, scale, bias)
        one = jax.jit(lambda *a: hc.maps(*a, **dict(HOW, iters=1)))(
            X, phi, scale, bias)
    want = reference_maps(X, phi, scale, bias)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)
    h_pre, h_post, h_res = got
    assert h_res.shape == (BB, T, N, N) and float(h_res.min()) > 0
    assert 0 < float(h_pre.min()) and float(h_pre.max()) < 1
    assert 0 < float(h_post.min()) and float(h_post.max()) < 2
    rows20, cols20 = (float(e) for e in hc.sum_errors(h_res))
    rows1, cols1 = (float(e) for e in hc.sum_errors(one[2]))
    # (a column that sums to little before its division keeps hc_eps's
    # share of it: after one iteration that can be 1e-4, after 20 it is not)
    assert cols20 < 1e-5 and cols1 < 1e-3
    assert rows1 > 1e-3 and rows20 * 10 <= rows1
    np.testing.assert_allclose(
        [rows20, cols20], ref.sum_errors(want[2]), atol=1e-5)
    # the maps move with the input: far from uniform, far from a permutation
    assert float(jnp.std(h_res[..., 0, 0])) > 0.01
    assert float(h_res.mean()) == pytest.approx(0.25, abs=1e-4)


def test_the_clamp_holds_h_res_finite():
    X, phi, _, bias = arrays(2)
    scale = jnp.asarray([1.0, 1.0, 400.0], jnp.float32)
    h_res = hc.maps(X, phi, scale, bias, **HOW)[2]
    assert bool(jnp.isfinite(h_res).all())
    assert float(hc.sum_errors(h_res)[1]) < 1e-4


def _plain_stats(X, phi):
    xf = jnp.concatenate([X[j] for j in range(N)], -1).astype(jnp.float32)
    return jnp.sum(xf * xf, -1), xf @ phi.astype(jnp.float32)


def _vjp_both(f, g, args, seed):
    """Outputs and every argument's cotangent of f and of g under one
    random output cotangent."""
    out_f, vjp_f = jax.vjp(f, *args)
    out_g, vjp_g = jax.vjp(g, *args)
    rng = np.random.default_rng(seed)
    ct = jax.tree_util.tree_map(
        lambda o: jnp.asarray(rng.normal(0, 1, o.shape), o.dtype), out_g)
    return (out_f, vjp_f(ct)), (out_g, vjp_g(ct))


def _close(got, want, tol):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32)))) + 1e-30
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - w.astype(jnp.float32)))) <= tol * scale


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_the_custom_vjps_are_their_jnp_forms_forward_and_backward(dtype, tol):
    """`stream_stats`, `pre` and `post`: values and every cotangent against
    the plain expression under jax's own autodiff."""
    X, phi, scale, bias = arrays(3, dtype)
    y = X[1] * 0.5
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = hc.maps(X, phi, scale, bias, **HOW)
        got, want = _vjp_both(hc.stream_stats, _plain_stats, (X, phi), 4)
        _close(got, want, tol)
        got, want = _vjp_both(hc.pre, hc._pre_jnp, (X, h_pre), 5)
        _close(got, want, tol)
        got, want = _vjp_both(hc.post, hc._post_jnp, (X, y, h_res, h_post),
                              6)
        _close(got, want, tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,cols", [(48, 128), (300, 1024)])
def test_the_pallas_kernels_are_their_jnp_forms(dtype, rows, cols):
    """`hc_pre_fwd` and `hc_post_fwd` through the Pallas interpreter: one
    block, and a grid with a ragged last block of rows (300 = 256 + 44) and
    two blocks of columns."""
    rng = np.random.default_rng(7)
    f = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.float32)
    X, y = f(N, 2, rows, cols).astype(dtype), f(2, rows, cols).astype(dtype)
    h_pre = jax.nn.sigmoid(f(2, rows, N))
    h_post = 2 * jax.nn.sigmoid(f(2, rows, N))
    h_res = jax.nn.softmax(f(2, rows, N, N), -1)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    _close(hc._pre_fused(X, h_pre, interpret=True), hc._pre_jnp(X, h_pre),
           tol)
    _close(hc._post_fused(X, y, h_res, h_post, interpret=True),
           hc._post_jnp(X, y, h_res, h_post), tol)
    assert hc.supported(X.shape) and not hc.supported((N, 2, rows, 48))


def test_expand_and_reduce():
    x = jnp.arange(12, dtype=jnp.float32).reshape(2, 2, 3)
    X = hc.expand(x, N)
    assert X.shape == (N, 2, 2, 3)
    np.testing.assert_array_equal(X[2], x)
    np.testing.assert_array_equal(hc.reduce(X), N * x)
    # summed in float32, rounded once: 1 + 3 x 2^-9 is 1 + 2^-7 in bfloat16's
    # 8 bits only if the three small terms are added before the rounding
    b = jnp.asarray([1.0, 2 ** -9, 2 ** -9, 2 ** -9], jnp.bfloat16)
    got = hc.reduce(b.reshape(4, 1, 1, 1))
    assert got.dtype == jnp.bfloat16
    assert float(got[0, 0, 0]) == float(jnp.asarray(
        1.0 + 3 * 2.0 ** -9, jnp.bfloat16))


# -- the residual piece's two forms around one branch ---------------------------

def test_the_plain_form_is_x_plus_f_to_the_bit_and_hands_the_rest_through():
    x = jnp.asarray(np.random.default_rng(8).normal(0, 1, (2, 8, 16)),
                    jnp.float32)
    F = lambda u: (jnp.tanh(u) * 3.0, jnp.sum(u), jnp.int32(7))
    y, s, k = pieces.PLAIN.half(F, x, under="attn/out")
    np.testing.assert_array_equal(y, x + jnp.tanh(x) * 3.0)
    assert float(s) == float(jnp.sum(x)) and int(k) == 7
    # a branch that returns its output alone, and a model's own add
    np.testing.assert_array_equal(pieces.PLAIN.half(jnp.tanh, x),
                                  x + jnp.tanh(x))
    got = pieces.PLAIN.half(jnp.tanh, x, join=lambda a, b: a + 0.22 * b)
    np.testing.assert_array_equal(got, x + 0.22 * jnp.tanh(x))
    assert pieces.PLAIN.leaves() == [] and pieces.PLAIN.extra == 0
    assert pieces.PLAIN.record() is None


def test_the_four_stream_form_is_the_references_half_layer():
    """`HyperConnection.half` around a branch against `ref.hc_half` around
    the same branch, values and the gradients of X and of the three leaves."""
    cfg = xing4_0_tiny(hidden_size=32, hc_sinkhorn_iters=20)
    path = pieces.HyperConnection(cfg)
    rng = np.random.default_rng(9)
    X = jnp.asarray(rng.normal(0, 1, (4, 2, 8, 32)), jnp.float32)
    path.phi.data = path.phi.data * 5.0
    path.bias.data = path.bias.data + jnp.asarray(rng.normal(0, 0.3, (24,)),
                                                  jnp.float32)
    hw = [t.data for t in path.leaves()]
    F = lambda u: jnp.tanh(u) * 2.0
    a = ref_arch()

    def program(X_, *hw_):
        out, errs = path.half(lambda u: (F(u),), X_, hw_)
        return jnp.sum(jnp.sin(out)), errs

    def plain(X_, *hw_):
        w = dict(zip(("phi", "scale", "bias"), hw_))
        out, _, errs = ref.hc_half(w, jnp.swapaxes(X_, 0, 1),
                                   lambda u: (F(u), None), a)
        return jnp.sum(jnp.sin(out)), errs

    with jax.default_matmul_precision("highest"):
        (got, errs), g_got = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1, 2, 3), has_aux=True))(X, *hw)
        (want, errs_w), g_want = jax.jit(jax.value_and_grad(
            plain, argnums=(0, 1, 2, 3), has_aux=True))(X, *hw)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_allclose(errs, errs_w, atol=1e-6)
    for g, w in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(w))) > 0
        _close(g, w, 2e-4)
    assert path.extra == 1 and len(path.leaves()) == 3
    path.record(errs)
    np.testing.assert_array_equal(path.res_sum_err.data, errs)
