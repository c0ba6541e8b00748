"""kernels/short_conv.py: the two Mosaic kernels (short causal convolution,
SiLU, the heads' L2 norm; forward and backward), through the Pallas
interpreter, against the module's plain `jax.numpy` route, and the choice
between the routes. The plain route itself is held to
`tests/reference/solar_open2.py` by `tests/test_solar_open2.py`. The
state-space layer's form of the same kernels (a bias, no norm, the
outputs by widths) likewise; its plain route is held to
`chipbench/reference_granitemoehybrid.py` by `tests/test_granite_hybrid.py`.
The gated short convolution's form (two gates, no bias, no activation:
`gate_conv_gate`): its plain route against the equations in three lines,
the same two kernel bodies against the plain route, forward and all four
gradients (dB, dC, dX of the one cotangent, and dw)."""
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


# -- the gated short convolution's form: C * conv(B * X) ----------------------

GC = 256                                 # channels: two tiles of 128
GBLOCK = sc._block_rows(10 ** 6, GC, 4, 7)


def _three_lines(bcx, w):
    """The equations as written: u = B * X, v_t = sum_j w_j u_{t-2+j},
    y = C * v, a sequence at a time, float64."""
    b, c, x = np.split(np.asarray(bcx, np.float64), 3, axis=-1)
    u = np.pad(b * x, ((0, 0), (w.shape[0] - 1, 0), (0, 0)))
    v = sum(u[:, j:j + bcx.shape[1]] * np.asarray(w, np.float64)[j]
            for j in range(w.shape[0]))
    return c * v


def _gated_out_and_grads(fn, bcx, w):
    """y and the cotangents of `bcx` (dB | dC | dX) and `w`, one program."""
    def run(a, b):
        y, vjp = jax.vjp(fn, a, b)
        return (y,) + vjp(jnp.cos(3.0 * y.astype(jnp.float32)).astype(
            y.dtype))
    return jax.jit(run)(bcx, w)


@pytest.mark.parametrize("B,T,taps", [(1, 9, 3), (3, 40, 3), (2, 17, 2)])
def test_gated_plain_route_is_the_equations(B, T, taps):
    """No bias and no activation; a sequence's first rows read zeros, not
    the sequence before."""
    ks = jax.random.split(jax.random.key(B * T), 2)
    bcx = jax.random.normal(ks[0], (B, T, 3 * 8))
    w = jax.random.uniform(ks[1], (taps, 8), minval=-0.5, maxval=0.5)
    got = sc.gate_conv_gate(bcx, w)
    assert got.shape == (B, T, 8) and got.dtype == bcx.dtype
    np.testing.assert_allclose(got, _three_lines(bcx, w), atol=1e-6)
    alone = jnp.concatenate([sc.gate_conv_gate(bcx[i:i + 1], w)
                             for i in range(B)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
    assert float(jnp.abs(sc.gate_conv_gate(jnp.zeros_like(bcx), w)).max()
                 ) == 0.0                  # no bias: zeros in, zeros out


@pytest.mark.parametrize("dtype,tol,T,B", [
    (jnp.float32, 1e-5, 2 * GBLOCK + 5, 2),   # the halo used, ragged, B > 1
    (jnp.float32, 1e-5, 2 * GBLOCK, 1),       # whole blocks
    (jnp.float32, 1e-5, 37, 2),               # shorter than a block
    (jnp.bfloat16, 2 ** -7, 2 * GBLOCK + 5, 2),
    (jnp.bfloat16, 2 ** -7, 37, 2)])
def test_gated_form_of_the_kernels_matches_the_plain_route(dtype, tol, T, B):
    """The value and all four gradients (dB, dC, dX as the three column
    ranges of one [B, T, 3C] cotangent; dw), in the operands' own dtypes,
    through the Pallas interpreter. B > 1: a sequence's first rows read
    zeros; T over several row blocks: the product B * X of the halo rows
    comes from the block before (forward: carried in the scratch;
    backward: made again from the second block of `bcx`)."""
    ks = jax.random.split(jax.random.key(T + B), 2)
    bcx = jax.random.normal(ks[0], (B, T, 3 * GC)).astype(dtype)
    w = jax.random.uniform(ks[1], (3, GC), minval=-0.5,
                           maxval=0.5).astype(dtype)
    got = _gated_out_and_grads(lambda a, b: sc._fused_gated(a, b, True),
                               bcx, w)
    want = _gated_out_and_grads(sc._plain_gated, bcx, w)
    assert got[0].shape == (B, T, GC) and got[1].shape == bcx.shape
    assert got[2].shape == w.shape
    for a, e in zip(got, want):
        assert a.shape == e.shape and a.dtype == e.dtype
        e = np.asarray(e, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), e,
                                   atol=tol * np.abs(e).max())
    # each third of the cotangent is its own gradient, none of them zero
    for third in np.split(np.asarray(got[1], np.float32), 3, axis=-1):
        assert np.abs(third).max() > 0.1


def test_the_gated_route_is_chosen_from_the_platform_and_the_width(
        monkeypatch):
    """On a TPU 2048 channels (a multiple of 128) go through the kernels;
    64 (`lfm2_moe_tiny`) take the plain route there too, as everything
    does on the CPU."""
    taken = []
    monkeypatch.setattr(
        sc, "_fused_gated",
        lambda *a: taken.append("kernels") or sc._plain_gated(*a[:2]))
    for on_tpu, c, want in ((True, 64, []), (True, 256, ["kernels"]),
                            (False, 256, [])):
        monkeypatch.setattr(sc, "_on_tpu", lambda on_tpu=on_tpu: on_tpu)
        del taken[:]
        y = sc.gate_conv_gate(jnp.ones((2, 5, 3 * c)), jnp.ones((3, c)))
        assert taken == want and y.shape == (2, 5, c)
        np.testing.assert_array_equal(np.asarray(y[0, :, 0]),
                                      [1, 2, 3, 3, 3])


def test_the_other_two_forms_block_sizes_are_what_they_were():
    """`_block_rows`' default counts three operands a row, as before the
    gated form (seven) was written: Solar's and Granite's calls keep their
    grids."""
    assert sc._block_rows(32768, 1536, 2) == 256        # Solar's call
    assert sc._block_rows(32768, 4352, 2) == 64         # Granite's
    assert sc._block_rows(4096, 2048, 2, 7) == 128      # this form's cell
