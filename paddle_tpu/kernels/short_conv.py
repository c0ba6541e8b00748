"""The delta-rule layer's way from the q|k|v projection to its operator:
a short causal depthwise convolution, SiLU, and an L2 norm over each head
of q and k.

    y_t  = sum_j w_j * pre_{t - (taps-1) + j}        (zeros before t = 0)
    a    = y * sigmoid(y)
    q, k = a * rsqrt(sum over the head's channels of a^2 + 1e-6);  v = a

`pre` [B, T, 3 * heads * d] holds the q, k and v columns of `heads` heads
side by side, `w` [taps, 3 * heads * d] a tap a row.

What runs where. On a TPU, at head widths that tile (d a multiple of
128), each pass is ONE Pallas kernel a call, row-blocked over T, and the
function a `jax.custom_vjp` over the two that keeps `pre` and `w` only:
  * `kda_conv_fwd`, grid (batch, blocks of rows), the blocks in order: a
    block of `pre` is widened to float32 into a VMEM scratch whose first
    eight rows are the block before's last, so that tap j is the same
    scratch read j rows further down; the taps' sum, SiLU and the norm
    run 32 rows at a time, every head of them in one turn of a loop, in
    registers, and q, k, v are written once, in `pre`'s dtype;
  * `kda_conv_bwd`, the same grid from the last block: it makes y and
    the norm's scale again, writes y's cotangent into a second scratch
    whose LAST eight rows are the block after's first (dpre_t reads the
    cotangents of y_t .. y_{t + taps-1}), and from there dpre, in `pre`'s
    dtype. dw leaves the kernel as eight partial sums a tap (a sublane
    each, summed outside), float32, added up over the blocks in an
    output block that stays in VMEM. The taps - 1 rows of `pre` before
    the block, which the walk back has not seen, come as a second,
    16-row block of the same array.
Everywhere else (the CPU, head widths that do not tile) the same
equations are plain `jax.numpy` over the whole sequence, and jax's
transpose of them the backward. The route is decided from the platform
and the shapes alone.

What is rounded where, on both routes: everything between `pre` and the
one rounding of q, k, v is float32, and so is everything between their
cotangents and the one rounding of dpre and of dw.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tpu import LANES, SUBLANES, row_block
from ._tpu import on_tpu as _on_tpu

__all__ = ["conv_silu_l2norm"]

_F32 = jnp.float32
_EPS = 1e-6
_HALO = 8          # float32 rows of a tile: what a scratch keeps of a neighbour
_ROWS = 32         # rows a turn of the kernels' inner loop


# -- the plain route -----------------------------------------------------------

def _causal_conv_silu(x, w):
    """x [B, T, C], w [taps, C]: tap j multiplies x_{t - (taps-1) + j}."""
    taps, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(_F32)
    return jax.nn.silu(sum(xp[:, j:j + T] * w[j] for j in range(taps)))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _EPS)


def _plain(pre, w, heads):
    B, T, C = pre.shape
    act = _causal_conv_silu(pre, w).reshape(B, T, 3, heads, C // (3 * heads))
    q, k, v = act[:, :, 0], act[:, :, 1], act[:, :, 2]
    return tuple(x.astype(pre.dtype) for x in (_l2norm(q), _l2norm(k), v))


# -- the chip's route: one Mosaic kernel a pass --------------------------------

def _tiles(R, body, backwards=False):
    """body(r0) for r0 = 0, _ROWS, .. < R, or from the last down. A loop,
    and every head of the block inside one turn: a tile's values stay in
    registers, and one head's chain of operations runs in the shadow of
    the others' (a loop a head was half as fast on the chip: a turn then
    waits for its one chain)."""
    n = R // _ROWS

    def turn(it, _):
        at = n - 1 - it if backwards else it
        body(pl.multiple_of(at * _ROWS, _ROWS))
    jax.lax.fori_loop(0, n, turn, None)


def _shifted(ref, r0, cols, offsets):
    """ref[r0 + o : r0 + o + _ROWS, cols] for each o in `offsets`
    (0 <= o <= _HALO): a read starts at a whole tile, so the rows come as
    one aligned read of _ROWS + _HALO and a rotation an offset."""
    n = _ROWS + _HALO
    wide = ref[pl.ds(r0, n), cols]
    return [wide[o:o + _ROWS] if o % _HALO == 0
            else pltpu.roll(wide, n - o, 0)[:_ROWS] for o in offsets]


def _act(xs_ref, w, r0, cols):
    """The taps' reads and y of rows [r0, r0 + _ROWS) of the block, for
    the columns `cols`: xs_ref's row _HALO is the block's first, w a
    list of the taps' [1, d] rows."""
    xj = _shifted(xs_ref, r0, cols,
                  [_HALO - len(w) + 1 + j for j in range(len(w))])
    return xj, sum(x * w_j for x, w_j in zip(xj, w))


def _fwd_kernel(x_ref, w_ref, q_ref, k_ref, v_ref, xs_ref, *, d):
    R, taps = x_ref.shape[0], w_ref.shape[0]
    C, n = x_ref.shape[-1], q_ref.shape[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        xs_ref[:_HALO] = jnp.zeros((_HALO, C), _F32)

    xs_ref[_HALO:] = x_ref[...].astype(_F32)

    def tile(r0):
        for c in range(0, C, d):
            cols = slice(c, c + d)
            w = [w_ref[j:j + 1, cols] for j in range(taps)]
            y = _act(xs_ref, w, r0, cols)[1]
            a = y * jax.nn.sigmoid(y)
            if c < 2 * n:
                a = a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + _EPS)
            o_ref = (q_ref, k_ref, v_ref)[c // n]
            o_ref[pl.ds(r0, _ROWS), c % n:c % n + d] = a.astype(o_ref.dtype)

    _tiles(R, tile)
    xs_ref[:_HALO] = xs_ref[R:]


def _bwd_kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref, dx_ref,
                dw_ref, xs_ref, da_ref, *, d, T):
    R, taps = x_ref.shape[0], w_ref.shape[0]
    C, n = x_ref.shape[-1], dq_ref.shape[-1]
    blk = pl.num_programs(1) - 1 - pl.program_id(1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        da_ref[R:] = jnp.zeros((_HALO, C), _F32)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    x = x_ref[...].astype(_F32)
    inside = T - blk * R           # rows of the block that the sequence has
    if T % R:
        # the last block's rows past T: whatever they hold, they are
        # zeros here, and so are their cotangents below
        rows = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        x = jnp.where(rows < inside, x, 0.0)
    xs_ref[_HALO:] = x
    xs_ref[:_HALO] = jnp.where(
        blk > 0, halo_ref[...].astype(_F32)[halo_ref.shape[0] - _HALO:], 0.0)

    def tile(r0):
        for c in range(0, C, d):
            cols = slice(c, c + d)
            w = [w_ref[j:j + 1, cols] for j in range(taps)]
            xj, y = _act(xs_ref, w, r0, cols)
            s = jax.nn.sigmoid(y)
            da = (dq_ref, dk_ref, dv_ref)[c // n][
                pl.ds(r0, _ROWS), c % n:c % n + d].astype(_F32)
            if c < 2 * n:
                a = y * s
                r = jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + _EPS)
                da = r * da - a * (r * r * r * jnp.sum(
                    da * a, -1, keepdims=True))
            dy = da * (s * (1.0 + y * (1.0 - s)))
            if T % R:
                rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)
                dy = jnp.where(rows < inside, dy, 0.0)
            da_ref[pl.ds(r0, _ROWS), cols] = dy
            dx = sum(a_j * w_j for a_j, w_j in zip(_shifted(
                da_ref, r0, cols, [taps - 1 - j for j in range(taps)]), w))
            dx_ref[pl.ds(r0, _ROWS), cols] = dx.astype(dx_ref.dtype)
            # dw_j, eight partial sums a column: a sublane each
            for j, x_j in enumerate(xj):
                dw_ref[j * _HALO:(j + 1) * _HALO, cols] += sum(
                    (dy * x_j)[lo:lo + _HALO] for lo in range(0, _ROWS, _HALO))

    _tiles(R, tile, backwards=True)
    da_ref[R:] = da_ref[:_HALO]


def _block_rows(T, C, itemsize):
    """Rows of a block of either pass, a multiple of _ROWS: what fits the
    backward's three [rows, C] operands in the model's dtype, each
    double-buffered, and its two float32 scratches."""
    R = row_block(T, C * (6 * itemsize + 8))
    if R == T:                     # the whole sequence, and a ragged turn
        return -(-T // _ROWS) * _ROWS
    return max(_ROWS, R // _ROWS * _ROWS)


# jitted, so that a model's layers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _fused_fwd(pre, w, heads, interpret):
    B, T, C = pre.shape
    taps, n = w.shape[0], C // 3
    R = _block_rows(T, C, pre.dtype.itemsize)

    def rows(width):
        return pl.BlockSpec((None, R, width), lambda b, i: (b, i, 0))

    q, k, v = pl.pallas_call(
        functools.partial(_fwd_kernel, d=n // heads),
        grid=(B, pl.cdiv(T, R)),
        in_specs=[rows(C), pl.BlockSpec((taps, C), lambda b, i: (0, 0))],
        out_specs=[rows(n)] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, T, n), pre.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((R + _HALO, C), _F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="kda_conv_fwd")(pre, w.astype(_F32))
    return tuple(x.reshape(B, T, heads, -1) for x in (q, k, v)), (pre, w)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _fused_bwd(heads, interpret, saved, cts):
    pre, w = saved
    B, T, C = pre.shape
    taps, n = w.shape[0], C // 3
    R = _block_rows(T, C, pre.dtype.itemsize)
    N = pl.cdiv(T, R)
    per = R // SUBLANES        # 16-row blocks of `pre` a block of rows

    def rows(width):
        return pl.BlockSpec((None, R, width), lambda b, i: (b, N - 1 - i, 0))

    dpre, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, d=n // heads, T=T),
        grid=(B, N),
        in_specs=[rows(C),
                  pl.BlockSpec((None, SUBLANES, C), lambda b, i: (
                      b, jnp.maximum((N - 1 - i) * per - 1, 0), 0)),
                  pl.BlockSpec((taps, C), lambda b, i: (0, 0))]
        + [rows(n)] * 3,
        out_specs=[rows(C), pl.BlockSpec((None, taps * _HALO, C),
                                         lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(pre.shape, pre.dtype),
                   jax.ShapeDtypeStruct((B, taps * _HALO, C), _F32)],
        scratch_shapes=[pltpu.VMEM((R + _HALO, C), _F32)] * 2,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="kda_conv_bwd")(
            pre, pre, w.astype(_F32), *(x.reshape(B, T, n) for x in cts))
    dw = dw.reshape(B, taps, _HALO, C).sum((0, 2))
    return dpre, dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused(pre, w, heads, interpret=False):
    """The function through the two kernels; `interpret` runs them in the
    Pallas interpreter (the tests' way, on the CPU)."""
    return _fused_fwd(pre, w, heads, interpret)[0]


_fused.defvjp(_fused_fwd, _fused_bwd)


def conv_silu_l2norm(pre, w, heads):
    """pre [B, T, 3 * heads * d] (the q, k and v columns of `heads` heads),
    w [taps, 3 * heads * d]. Returns q, k, v [B, T, heads, d] in `pre`'s
    dtype, q and k of unit length a head."""
    d = pre.shape[-1] // (3 * heads)
    if _on_tpu() and d % LANES == 0:
        return _fused(pre, w, heads, False)
    return _plain(pre, w, heads)
