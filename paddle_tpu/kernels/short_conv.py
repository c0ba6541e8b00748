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

A second form, the state-space layer's (`conv_bias_silu`): the same
convolution with a bias a channel and no norm, a = silu(y + b), over the
x | B | C columns together, handed out as one output a part. It is the
same two kernel bodies (`ssm_conv_fwd`, `ssm_conv_bwd`) over the same
row-blocked tiles: the bias, the norm and the outputs' widths are
decided where the kernel is traced, so the delta-rule layer's kernels
are what they were; the bias's gradient leaves with dw, as the partial
sums of one more tap that reads ones.

A third form, the gated short convolution's (`gate_conv_gate`): no bias,
NO activation, two gates around the taps. `bcx` [B, T, 3 C] holds the
columns B | C | X of one projection side by side, `w` [taps, C]:

    u    = B * X
    v_t  = sum_j w_j * u_{t - (taps-1) + j}          (zeros before t = 0)
    y    = C * v                                     [B, T, C]

The same two kernel bodies again (`gate_conv_fwd`, `gate_conv_bwd`) over
the same row-blocked tiles: the product u is what the float32 scratch
holds, made in VMEM for the block's rows and for the halo's alike (the
forward carries the block before's last rows of u in the scratch, the
backward makes them from the second, 16-row block of `bcx`), the second
gate takes the activation's place, and the backward writes the three
column ranges of ONE [B, T, 3 C] cotangent (dB = du * X, dC = dy * v,
dX = du * B) from `bcx` and `w` alone; dw leaves as the same partial
sums. Memory-bound: a token reads 3 C and writes C forward. Which form a
body is traced for is decided where the kernel is traced, so the other
two forms' kernels are the programs they were.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tpu import LANES, SUBLANES, row_block
from ._tpu import on_tpu as _on_tpu

__all__ = ["conv_silu_l2norm", "conv_bias_silu", "gate_conv_gate"]

_F32 = jnp.float32
_EPS = 1e-6
_HALO = 8          # float32 rows of a tile: what a scratch keeps of a neighbour
_ROWS = 32         # rows a turn of the kernels' inner loop


# -- the plain route -----------------------------------------------------------

def _causal_conv_silu(x, w, bias=None):
    """x [B, T, C], w [taps, C]: tap j multiplies x_{t - (taps-1) + j}."""
    taps, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(_F32)
    y = sum(xp[:, j:j + T] * w[j] for j in range(taps))
    return jax.nn.silu(y if bias is None else y + bias.astype(_F32))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _EPS)


def _plain(pre, w, heads):
    B, T, C = pre.shape
    act = _causal_conv_silu(pre, w).reshape(B, T, 3, heads, C // (3 * heads))
    q, k, v = act[:, :, 0], act[:, :, 1], act[:, :, 2]
    return tuple(x.astype(pre.dtype) for x in (_l2norm(q), _l2norm(k), v))


def _plain_gated(bcx, w):
    """The third form over the whole sequence: bcx [B, T, 3 C], w [taps, C]."""
    C, T, taps = w.shape[1], bcx.shape[1], w.shape[0]
    x = bcx.astype(_F32)
    u = jnp.pad(x[..., :C] * x[..., 2 * C:], ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(_F32)
    v = sum(u[:, j:j + T] * w[j] for j in range(taps))
    return (x[..., C:2 * C] * v).astype(bcx.dtype)


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


def _places(widths, d):
    """(output, its first column) of each tile of d columns of the
    input, for outputs of `widths` columns side by side."""
    return [(i, c) for i, n in enumerate(widths) for c in range(0, n, d)]


def _taps_input(ref, C, gate):
    """What the taps read of a block of rows, float32 [rows, C]: the rows
    themselves, or with `gate` the product B * X of a block of B | C | X
    columns."""
    if not gate:
        return ref[...].astype(_F32)
    return ref[:, :C].astype(_F32) * ref[:, 2 * C:].astype(_F32)


def _fwd_kernel(x_ref, w_ref, *rest, d, normed, bias, gate=False):
    """`rest`: the bias [1, C] where there is one, the outputs (the
    input's columns side by side, the first `normed` of them normalised
    a tile of d), the scratch. With `gate` the input is B | C | X, the
    scratch holds B * X and the one output is C * (the taps' sum)."""
    b_ref = rest[0] if bias else None
    o_refs, xs_ref = rest[bias:-1], rest[-1]
    R, taps = x_ref.shape[0], w_ref.shape[0]
    C = xs_ref.shape[-1]
    places = _places([o.shape[-1] for o in o_refs], d)

    @pl.when(pl.program_id(1) == 0)
    def _():
        xs_ref[:_HALO] = jnp.zeros((_HALO, C), _F32)

    xs_ref[_HALO:] = _taps_input(x_ref, C, gate)

    def tile(r0):
        for c, (o, at) in zip(range(0, C, d), places):
            cols = slice(c, c + d)
            w = [w_ref[j:j + 1, cols] for j in range(taps)]
            y = _act(xs_ref, w, r0, cols)[1]
            if bias:
                y = y + b_ref[:, cols]
            if gate:
                a = y * x_ref[pl.ds(r0, _ROWS), C + c:C + c + d].astype(_F32)
            else:
                a = y * jax.nn.sigmoid(y)
            if c < normed:
                a = a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + _EPS)
            o_refs[o][pl.ds(r0, _ROWS), at:at + d] = a.astype(
                o_refs[o].dtype)

    _tiles(R, tile)
    xs_ref[:_HALO] = xs_ref[R:]


def _bwd_kernel(x_ref, halo_ref, w_ref, *rest, d, T, normed, bias,
                gate=False):
    """`rest`: the bias where there is one, the outputs' cotangents, then
    dx, dw (the bias's gradient in eight rows after the taps') and the
    two scratches. With `gate` x and dx are B | C | X wide: the scratch
    holds B * X, and dx's three column ranges are written from it."""
    b_ref = rest[0] if bias else None
    ct_refs = rest[bias:-4]
    dx_ref, dw_ref, xs_ref, da_ref = rest[-4:]
    R, taps = x_ref.shape[0], w_ref.shape[0]
    C = xs_ref.shape[-1]
    places = _places([ct.shape[-1] for ct in ct_refs], d)
    blk = pl.num_programs(1) - 1 - pl.program_id(1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        da_ref[R:] = jnp.zeros((_HALO, C), _F32)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    x = _taps_input(x_ref, C, gate)
    inside = T - blk * R           # rows of the block that the sequence has
    if T % R:
        # the last block's rows past T: whatever they hold, they are
        # zeros here, and so are their cotangents below
        rows = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        x = jnp.where(rows < inside, x, 0.0)
    xs_ref[_HALO:] = x
    xs_ref[:_HALO] = jnp.where(
        blk > 0, _taps_input(halo_ref, C, gate)[halo_ref.shape[0] - _HALO:],
        0.0)

    def tile(r0):
        for c, (o, at) in zip(range(0, C, d), places):
            cols = slice(c, c + d)
            w = [w_ref[j:j + 1, cols] for j in range(taps)]
            xj, y = _act(xs_ref, w, r0, cols)
            if bias:
                y = y + b_ref[:, cols]
            da = ct_refs[o][pl.ds(r0, _ROWS), at:at + d].astype(_F32)
            if gate:
                # y = C * v: the gate's own cotangent leaves here, the
                # taps' sum gets the other factor
                side = [x_ref[pl.ds(r0, _ROWS), g * C + c:g * C + c + d]
                        .astype(_F32) for g in range(3)]
                dx_ref[pl.ds(r0, _ROWS), C + c:C + c + d] = (da * y).astype(
                    dx_ref.dtype)
                dy = da * side[1]
            else:
                s = jax.nn.sigmoid(y)
                if c < normed:
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
            if gate:               # u = B * X: each factor's from the other
                for g in (0, 2):
                    dx_ref[pl.ds(r0, _ROWS), g * C + c:g * C + c + d] = (
                        dx * side[2 - g]).astype(dx_ref.dtype)
            else:
                dx_ref[pl.ds(r0, _ROWS), cols] = dx.astype(dx_ref.dtype)
            # dw_j, eight partial sums a column: a sublane each; the
            # bias's are those of a tap that reads ones
            for j, x_j in enumerate(xj + [None] * bias):
                dw_ref[j * _HALO:(j + 1) * _HALO, cols] += sum(
                    (dy if x_j is None else dy * x_j)[lo:lo + _HALO]
                    for lo in range(0, _ROWS, _HALO))

    _tiles(R, tile, backwards=True)
    da_ref[R:] = da_ref[:_HALO]


def _block_rows(T, C, itemsize, operands=3):
    """Rows of a block of either pass, a multiple of _ROWS: what fits the
    backward's `operands` [rows, C] operands in the model's dtype (three;
    the gated form's B | C | X, its cotangent and the output's: seven),
    each double-buffered, and its two float32 scratches; where the fit
    does not divide T and a block no less than half of it does, that one
    (a ragged last block costs the backward a select a tile)."""
    R = row_block(T, C * (2 * operands * itemsize + 8))
    if R == T:                     # the whole sequence, and a ragged turn
        return -(-T // _ROWS) * _ROWS
    R = max(_ROWS, R // _ROWS * _ROWS)
    whole = [r for r in range(R, R // 2, -_ROWS) if T % r == 0]
    return whole[0] if whole else R


def _call_fwd(pre, w, bias, widths, d, normed, name, interpret, gate=False):
    """The outputs [B, T, width] of the forward kernel: `pre`'s columns
    side by side (with `gate` the one output of the taps' width from
    B | C | X), a tile of d columns at a time."""
    B, T, wide = pre.shape
    taps, C = w.shape
    R = _block_rows(T, C, pre.dtype.itemsize, 7 if gate else 3)

    def rows(width):
        return pl.BlockSpec((None, R, width), lambda b, i: (b, i, 0))

    def whole(n):
        return pl.BlockSpec((n, C), lambda b, i: (0, 0))

    has_bias = bias is not None
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, normed=normed, bias=has_bias,
                          gate=gate),
        grid=(B, pl.cdiv(T, R)),
        in_specs=[rows(wide), whole(taps)] + [whole(1)] * has_bias,
        out_specs=[rows(n) for n in widths],
        out_shape=[jax.ShapeDtypeStruct((B, T, n), pre.dtype)
                   for n in widths],
        scratch_shapes=[pltpu.VMEM((R + _HALO, C), _F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=name)(
            pre, w.astype(_F32),
            *([bias.astype(_F32)[None]] if has_bias else []))


def _call_bwd(pre, w, bias, cts, d, normed, name, interpret, gate=False):
    """(dpre, dw [taps (+ 1 with a bias: its gradient), C] float32) of
    the backward kernel from the outputs' cotangents [B, T, width]."""
    B, T, wide = pre.shape
    taps, C = w.shape
    R = _block_rows(T, C, pre.dtype.itemsize, 7 if gate else 3)
    N = pl.cdiv(T, R)
    per = R // SUBLANES        # 16-row blocks of `pre` a block of rows

    def rows(width):
        return pl.BlockSpec((None, R, width), lambda b, i: (b, N - 1 - i, 0))

    def whole(n):
        return pl.BlockSpec((n, C), lambda b, i: (0, 0))

    has_bias = bias is not None
    sums = (taps + has_bias) * _HALO
    dpre, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, T=T, normed=normed,
                          bias=has_bias, gate=gate),
        grid=(B, N),
        in_specs=[rows(wide),
                  pl.BlockSpec((None, SUBLANES, wide), lambda b, i: (
                      b, jnp.maximum((N - 1 - i) * per - 1, 0), 0)),
                  whole(taps)] + [whole(1)] * has_bias
        + [rows(ct.shape[-1]) for ct in cts],
        out_specs=[rows(wide), pl.BlockSpec((None, sums, C),
                                            lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(pre.shape, pre.dtype),
                   jax.ShapeDtypeStruct((B, sums, C), _F32)],
        scratch_shapes=[pltpu.VMEM((R + _HALO, C), _F32)] * 2,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=name)(
            pre, pre, w.astype(_F32),
            *([bias.astype(_F32)[None]] if has_bias else []), *cts)
    return dpre, dw.reshape(B, taps + has_bias, _HALO, C).sum((0, 2))


# jitted, so that a model's layers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _fused_fwd(pre, w, heads, interpret):
    B, T, C = pre.shape
    n = C // 3
    q, k, v = _call_fwd(pre, w, None, (n, n, n), n // heads, 2 * n,
                        "kda_conv_fwd", interpret)
    return tuple(x.reshape(B, T, heads, -1) for x in (q, k, v)), (pre, w)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _fused_bwd(heads, interpret, saved, cts):
    pre, w = saved
    B, T, C = pre.shape
    n = C // 3
    dpre, dw = _call_bwd(pre, w, None, [x.reshape(B, T, n) for x in cts],
                         n // heads, 2 * n, "kda_conv_bwd", interpret)
    return dpre, dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused(pre, w, heads, interpret=False):
    """The function through the two kernels; `interpret` runs them in the
    Pallas interpreter (the tests' way, on the CPU)."""
    return _fused_fwd(pre, w, heads, interpret)[0]


_fused.defvjp(_fused_fwd, _fused_bwd)


# the state-space layer's form: a bias, no norm, the outputs by widths

@functools.partial(jax.jit, static_argnames=("widths", "interpret"))
def _bias_fwd(pre, w, bias, widths, interpret):
    outs = _call_fwd(pre, w, bias, widths, LANES, 0, "ssm_conv_fwd",
                     interpret)
    return tuple(outs), (pre, w, bias)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _bias_bwd(widths, interpret, saved, cts):
    pre, w, bias = saved
    dpre, dw = _call_bwd(pre, w, bias, list(cts), LANES, 0, "ssm_conv_bwd",
                         interpret)
    return dpre, dw[:-1].astype(w.dtype), dw[-1].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_bias(pre, w, bias, widths, interpret=False):
    return _bias_fwd(pre, w, bias, widths, interpret)[0]


_fused_bias.defvjp(_bias_fwd, _bias_bwd)


# the gated short convolution's form: two gates, no bias, no activation

@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_fwd(bcx, w, interpret):
    (y,) = _call_fwd(bcx, w, None, (w.shape[1],), LANES, 0, "gate_conv_fwd",
                     interpret, gate=True)
    return y, (bcx, w)


@functools.partial(jax.jit, static_argnums=(0,))
def _gated_bwd(interpret, saved, dy):
    bcx, w = saved
    dbcx, dw = _call_bwd(bcx, w, None, [dy], LANES, 0, "gate_conv_bwd",
                         interpret, gate=True)
    return dbcx, dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_gated(bcx, w, interpret=False):
    return _gated_fwd(bcx, w, interpret)[0]


_fused_gated.defvjp(_gated_fwd, _gated_bwd)


def gate_conv_gate(bcx, w):
    """C * causal conv(B * X), no bias and no activation: bcx [B, T, 3 C]
    (the columns B | C | X of one projection), w [taps, C]. Returns
    y [B, T, C] in `bcx`'s dtype; a sequence's first rows read zeros."""
    if _on_tpu() and w.shape[1] % LANES == 0:
        return _fused_gated(bcx, w, False)
    return _plain_gated(bcx, w)


def conv_bias_silu(pre, w, bias, widths):
    """silu(causal conv(pre) + bias), its columns handed out side by side:
    pre [B, T, C], w [taps, C], bias [C], `widths` a tuple that sums to
    C. Returns one [B, T, width] a width, in `pre`'s dtype."""
    if _on_tpu() and all(n % LANES == 0 for n in widths):
        return _fused_bias(pre, w, bias, tuple(widths), False)
    a = _causal_conv_silu(pre, w, bias).astype(pre.dtype)
    edges = [sum(widths[:i]) for i in range(len(widths) + 1)]
    return tuple(a[..., lo:hi] for lo, hi in zip(edges, edges[1:]))


def conv_silu_l2norm(pre, w, heads):
    """pre [B, T, 3 * heads * d] (the q, k and v columns of `heads` heads),
    w [taps, 3 * heads * d]. Returns q, k, v [B, T, heads, d] in `pre`'s
    dtype, q and k of unit length a head."""
    d = pre.shape[-1] // (3 * heads)
    if _on_tpu() and d % LANES == 0:
        return _fused(pre, w, heads, False)
    return _plain(pre, w, heads)
