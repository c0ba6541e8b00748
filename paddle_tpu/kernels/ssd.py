"""Chunked state-space duality (the Mamba-2 "SSD" operator), forward and
backward.

Per head, with a state S in R^{N x P} (N the state size, P the head
width), a step dt_t > 0, a rate A < 0 and ONE B_t, C_t in R^N shared by
all heads (one group):

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T
    y_t = S_t^T C_t + D x_t

The recurrence is sequential in t. This operator runs it a CHUNK of Q
tokens at a time: with a_t = dt_t A, G_t the running sum of a inside the
chunk (`chunk_cumsum`) and S_0 the state the chunk starts from,

    L_ij = exp(G_i - G_j)                                   (j <= i)
    Y    = (L o (C B^T)) (dt x) + exp(G) (C S_0) + D x
    S_Q  = exp(G_Q) S_0 + (B exp(G_Q - G))^T (dt x)

exp(G_i - G_j) is computed pairwise and never split into
exp(G_i) exp(-G_j), which overflows under a strong decay.

What runs where. On a TPU, at head widths that fill or divide the 128
lanes and a state that is a multiple of them, each pass is ONE Pallas
kernel a call, and the operator a `jax.custom_vjp` over the two:
  * `ssd_chunk_scan`, grid (batch, chunks, blocks of heads), the chunks
    in order and every head's state [N, P] in VMEM across them
    (64 heads x 128 x 64 float32 = 2 MB). x and y stay [B, T, H * P] in
    the model's dtype: a block of heads is a lane slice. C B^T is made
    once a chunk (the first block of heads leaves it in VMEM), L a head
    and a block of 128 rows at a time, only the blocks on and under the
    diagonal, and never written to HBM (whole it is heads x chunks x Q x
    Q x 4 bytes: 2.1 GB a layer at 64 x 128 x 256). The products that do
    not depend on the head (C S_0, B^T (w dt x)) run over all the
    block's heads at once; the masked product is a head's own, and two
    heads of width 64 share a tile of 128 lanes: each multiplies the
    whole tile and keeps its half, which costs the MXU what one head
    would. Differentiated, it also writes each chunk's starting state.
  * `ssd_chunk_scan_bwd`, the same grid from the last chunk, the state's
    cotangent in VMEM: dx in the model's dtype, d dt and dG a column a
    head, dB and dC added up over the blocks of heads in VMEM (float32),
    D's gradient as eight partial sums a chunk. dG needs no [Q, Q]
    reduction: sum_j dL_ij L_ij = sum_p dy_ip (y - D x)_ip and
    sum_i dL_ij L_ij = sum_p (dt x)_jp d(dt x)_jp; the two all but
    cancel, so both are taken of the operands as the products saw them.
Everywhere else (the CPU, other widths) the same equations are plain
`jax.numpy` einsums over all chunks at once, a `lax.scan` for the state
between chunks, and jax's transpose of that the backward. The route is
decided from the platform and the shapes alone.

What is rounded where, on both routes: matmul operands take the dtype of
`x` (bf16 in a bf16 model; float32 stays float32 and multiplies at full
precision), accumulation, dt, G, L and the state are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tpu import LANES
from ._tpu import on_tpu as _on_tpu

__all__ = ["chunk_cumsum", "ssd_chunk_scan"]

_F32 = jnp.float32
_SUB = 128         # rows of a block of L; the chunk is a multiple (or less)
_HEADS = 8         # heads a grid step
_NN = (((1,), (0,)), ((), ()))     # a @ b
_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b


def _pad_t(x, pad, mode="constant"):
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                   mode=mode)


def chunk_cumsum(dt, A, chunk):
    """G [B, T, H] float32: the running sum of dt_t * A_h inside each
    chunk of `chunk` tokens (inclusive). dt [B, T, H] (after its
    softplus), A [H] (< 0)."""
    B, T, H = dt.shape
    pad = -T % chunk
    a = _pad_t(dt.astype(_F32) * A.astype(_F32), pad)
    G = jnp.cumsum(a.reshape(B, -1, chunk, H), axis=2)
    return G.reshape(B, T + pad, H)[:, :T]


# -- the plain route -----------------------------------------------------------

def _plain(x, dt, G, Bm, Cm, D, chunk):
    """All chunks at once; x [B, T, H, P] with T a multiple of `chunk`."""
    B, T, H, P = x.shape
    n = T // chunk
    hi = jax.lax.Precision.HIGHEST
    x32 = x.astype(_F32)
    xd = (x32 * dt[..., None]).reshape(B, n, chunk, H, P)
    G = G.reshape(B, n, chunk, H)
    Bc = Bm.astype(_F32).reshape(B, n, chunk, -1)
    Cc = Cm.astype(_F32).reshape(B, n, chunk, -1)
    low = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.exp(jnp.where(low[None, None, :, :, None],
                          G[:, :, :, None] - G[:, :, None, :], -jnp.inf))
    CB = jnp.einsum("bnis,bnjs->bnij", Cc, Bc, precision=hi)
    y = jnp.einsum("bnij,bnijh,bnjhp->bnihp", CB, L, xd, precision=hi)
    G_end = G[:, :, -1]                                       # [B, n, H]
    add = jnp.einsum("bnjs,bnjh,bnjhp->bnhsp", Bc,
                     jnp.exp(G_end[:, :, None] - G), xd, precision=hi)

    def step(S, xs):
        decay, new = xs
        return S * decay[..., None, None] + new, S

    S0 = jnp.zeros((B, H, Bc.shape[-1], P), _F32)
    _, starts = jax.lax.scan(
        step, S0, (jnp.moveaxis(jnp.exp(G_end), 1, 0),
                   jnp.moveaxis(add, 1, 0)))
    y = y + jnp.einsum("bnis,nbhsp,bnih->bnihp", Cc, starts, jnp.exp(G),
                       precision=hi)
    y = y.reshape(B, T, H, P) + x32 * D.astype(_F32)[:, None]
    return y.astype(x.dtype)


# -- the chip's route: one Mosaic kernel a pass --------------------------------
#
# Grid (batch, chunks, blocks of heads): the chunk axis is walked in
# order, the blocks of heads inside it, so what does not depend on the
# head (C B^T, and in the backward dB and dC) is made or finished once a
# chunk. dt and G go twice: a column a head ([B, H / heads, T, heads]:
# what scales a token's row) and, G only, a row a head ([B, H, T]: the j
# of L_ij).

def _dot(a, b, dims=_NN):
    """Operands as they come, float32 out; two float32 operands multiply
    at full precision, bf16 operands in the MXU's one pass."""
    full = a.dtype == _F32 and b.dtype == _F32
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=_F32,
        precision=jax.lax.Precision.HIGHEST if full else None)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _tile(P):
    """(lanes of a tile of heads, heads in it): two heads of width 64
    share the 128 lanes."""
    tw = max(P, LANES)
    return tw, tw // P


def _spread(cols, P):
    """[R, heads] -> [R, heads * P]: head h's column over its P lanes."""
    tw, per = _tile(P)
    which = _iota((1, tw), 1) // P
    tiles = []
    for t in range(cols.shape[1] // per):
        v = cols[:, t * per:t * per + 1]
        for k in range(1, per):
            v = jnp.where(which == k, cols[:, t * per + k:t * per + k + 1], v)
        tiles.append(jnp.broadcast_to(v, (cols.shape[0], tw)))
    return _cat(tiles, 1)


def _head_sums(v, P):
    """[R, heads * P] -> [R, heads]: the sum over each head's P lanes."""
    tw, per = _tile(P)
    which = _iota((1, tw), 1) // P
    out = []
    for t in range(v.shape[1] // tw):
        tile = v[:, t * tw:(t + 1) * tw]
        for k in range(per):
            out.append(jnp.sum(tile if per == 1 else
                               jnp.where(which == k, tile, 0.0), 1,
                               keepdims=True))
    return out


def _decay_blocks(Gc, gr_ref, h):
    """[(first row, L's block [s, first row + s] float32)] of head h of
    the grid step: the blocks of L on and under the diagonal, a block of
    rows at a time. Gc [Q, 1] the head's column of G, gr_ref [heads, Q]
    a row a head (read a block of lanes at a time: a slice of a loaded
    row that starts past the first tile has no layout)."""
    Q = Gc.shape[0]
    s = min(_SUB, Q)
    low = _iota((s, s), 0) >= _iota((s, s), 1)
    out = []
    for lo in range(0, Q, s):
        rows = Gc[lo:lo + s]
        diag = jnp.exp(jnp.where(low, rows - gr_ref[h:h + 1, lo:lo + s],
                                 -jnp.inf))
        out.append((lo, jnp.concatenate(
            [jnp.exp(rows - gr_ref[h:h + 1, :lo]), diag], 1) if lo else diag))
    return out


def _cat(parts, axis):
    return jnp.concatenate(parts, axis) if len(parts) > 1 else parts[0]


def _fwd_kernel(x_ref, dtc_ref, gc_ref, gr_ref, b_ref, c_ref, d_ref, y_ref,
                *rest, P):
    """One chunk of one block of heads. `rest`: the chunk's starting
    state (the backward's residual) where it is asked for, then the
    scratch: every block's state, and C B^T."""
    st_ref, cb_ref = rest[-2:]
    n, hb = pl.program_id(1), pl.program_id(2)

    @pl.when((n == 0) & (hb == 0))
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    @pl.when(hb == 0)
    def _():
        cb_ref[...] = _dot(c_ref[...], b_ref[...], _NT)

    dtype = x_ref.dtype
    Q, W = x_ref.shape
    tw, per = _tile(P)
    which = _iota((1, tw), 1) // P
    x = x_ref[...].astype(_F32)
    gc = gc_ref[...]
    xd = x * _spread(dtc_ref[...], P)
    xdc = xd.astype(dtype)
    tiles = []
    for t in range(W // tw):
        xt, yt = xdc[:, t * tw:(t + 1) * tw], None
        for k in range(per):
            h = t * per + k
            yh = _cat([_dot((cb_ref[lo:lo + L.shape[0], :L.shape[1]] * L
                             ).astype(dtype), xt[:L.shape[1]])
                       for lo, L in _decay_blocks(gc[:, h:h + 1], gr_ref,
                                                  h)], 0)
            yt = yh if yt is None else jnp.where(which == k, yh, yt)
        tiles.append(yt)
    y = _cat(tiles, 1)
    st = st_ref[hb]
    if len(rest) > 2:
        rest[0][...] = st
    y = (y + _spread(jnp.exp(gc), P) * _dot(c_ref[...], st.astype(dtype))
         + d_ref[...] * x)
    y_ref[...] = y.astype(y_ref.dtype)
    to_end = _spread(jnp.exp(gc[-1:] - gc), P)
    st_ref[hb] = (st * _spread(jnp.exp(gc[-1:]), P)
                  + _dot(b_ref[...], (xd * to_end).astype(dtype), _TN))


def _bwd_kernel(x_ref, dtc_ref, gc_ref, gr_ref, b_ref, c_ref, d_ref, dy_ref,
                s0_ref, dx_ref, ddt_ref, dg_ref, db_ref, dc_ref, dd_ref,
                ds_ref, cb_ref, dcb_ref, *, P):
    """One chunk of the walk back: ds_ref holds the cotangent of the
    state the chunk ENDS with, a block of heads each."""
    n, hb = pl.program_id(1), pl.program_id(2)

    @pl.when((n == 0) & (hb == 0))
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    @pl.when(hb == 0)
    def _():
        cb_ref[...] = _dot(c_ref[...], b_ref[...], _NT)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    dtype = x_ref.dtype
    Q, W = x_ref.shape
    tw, per = _tile(P)
    which = _iota((1, tw), 1) // P
    x, dyc = x_ref[...].astype(_F32), dy_ref[...]
    dy = dyc.astype(_F32)
    gc = gc_ref[...]
    dt = _spread(dtc_ref[...], P)
    xd = x * dt
    xdc = xd.astype(dtype)
    y_tiles, dxd_tiles = [], []
    for t in range(W // tw):
        lanes = slice(t * tw, (t + 1) * tw)
        xt, dyt = xdc[:, lanes], dyc[:, lanes]
        yt = dxt = None
        for k in range(per):
            h = t * per + k
            mine = dyt if per == 1 else jnp.where(which == k, dyt,
                                                  jnp.zeros_like(dyt))
            yh, dxh = [], jnp.zeros((Q, tw), _F32)
            for lo, L in _decay_blocks(gc[:, h:h + 1], gr_ref, h):
                s, width = L.shape
                M = (cb_ref[lo:lo + s, :width] * L).astype(dtype)
                yh.append(_dot(M, xt[:width]))
                back = _dot(M, dyt[lo:lo + s], _TN)           # [width, tw]
                if width < Q:
                    back = jnp.concatenate(
                        [back, jnp.zeros((Q - width, tw), _F32)], 0)
                dxh = dxh + back
                dcb_ref[lo:lo + s, :width] += _dot(
                    mine[lo:lo + s], xt[:width], _NT) * L
            yh = _cat(yh, 0)
            yt = yh if yt is None else jnp.where(which == k, yh, yt)
            dxt = dxh if dxt is None else jnp.where(which == k, dxh, dxt)
        y_tiles.append(yt)
        dxd_tiles.append(dxt)
    y, dxd = _cat(y_tiles, 1), _cat(dxd_tiles, 1)

    st, ds = s0_ref[...], ds_ref[hb]
    e = _spread(jnp.exp(gc), P)
    to_end = _spread(jnp.exp(gc[-1:] - gc), P)
    decay = _spread(jnp.exp(gc[-1:]), P)                      # [1, W]
    # dG inside the chunk is sum_j R_ij - sum_i R_ij of ONE R = dL o L;
    # the two sums all but cancel, so both are taken of the operands the
    # products above saw, rounded as they were: (dt x) as the MXU read
    # it, not the float32 it was rounded from
    inside = dy * y - dxd * xdc.astype(_F32)
    y = e * _dot(c_ref[...], st.astype(dtype))       # the state's part of y
    z = _dot(b_ref[...], ds.astype(dtype)) * to_end           # [Q, W]
    dye = (dy * e).astype(dtype)
    ds_ref[hb] = ds * decay + _dot(c_ref[...], dye, _TN)
    dc_ref[...] += _dot(dye, st.astype(dtype), _NT)
    db_ref[...] += _dot((xd * to_end).astype(dtype), ds.astype(dtype), _NT)
    dxd = dxd + z
    dx_ref[...] = (dxd * dt + d_ref[...] * dy).astype(dx_ref.dtype)
    g_own = inside + dy * y - z * xd
    # G_Q's own: through every token's reach to the chunk's end, and
    # through the decay of the starting state
    end = (jnp.sum(z * xd, 0, keepdims=True)
           + decay * jnp.sum(ds * st, 0, keepdims=True))      # [1, W]
    last = _iota((Q, 1), 0) == Q - 1
    for h, (dt_h, own, end_h) in enumerate(zip(
            _head_sums(dxd * x, P), _head_sums(g_own, P),
            _head_sums(end, P))):
        ddt_ref[:, h:h + 1] = dt_h
        dg_ref[:, h:h + 1] = own + jnp.where(last, end_h, 0.0)
    dyx = dy * x
    dd_ref[...] = sum(dyx[lo:lo + 8] for lo in range(0, Q, 8))

    @pl.when(hb == pl.num_programs(2) - 1)
    def _():
        dcb = dcb_ref[...].astype(dtype)
        dc_ref[...] += _dot(dcb, b_ref[...])
        db_ref[...] += _dot(dcb, c_ref[...], _TN)


# how an operand goes a block a grid step (b, i, h): chunk `at(i)`, block
# of heads h
_WIDE, _GROUP, _COL, _ROW, _LANES, _OWN = range(6)
_INS = (_WIDE, _COL, _COL, _ROW, _GROUP, _GROUP, _LANES)


def _call(kernel, name, backwards, ins, kinds, outs, out_kinds, scratch,
          P, chunk, interpret):
    """`kernel` over the grid (B, T / chunk, H / heads), heads = _HEADS,
    first chunk first or last. _WIDE: [B, T, H * P], a chunk's
    [Q, heads * P]; _GROUP: [B, T, N] (B, C and their gradients), a
    chunk's [Q, N] whatever the block of heads; _COL: [B, H / heads, T,
    heads], a chunk's [Q, heads]; _ROW: [B, H, T], a chunk's [heads, Q]; _LANES:
    [1, H * P] (D), the block's lanes; _OWN: [B, n, H / heads, r,
    heads * P] (states, D's partial sums), the chunk's block's own."""
    B, T, HP = ins[0].shape
    n, W, heads = T // chunk, _HEADS * P, _HEADS

    def at(i):
        return n - 1 - i if backwards else i

    def spec(x, kind):
        if kind == _WIDE:
            return pl.BlockSpec((None, chunk, W),
                                lambda b, i, h: (b, at(i), h))
        if kind == _GROUP:
            return pl.BlockSpec((None, chunk, x.shape[2]),
                                lambda b, i, h: (b, at(i), 0))
        if kind == _COL:
            return pl.BlockSpec((None, None, chunk, heads),
                                lambda b, i, h: (b, h, at(i), 0))
        if kind == _ROW:
            return pl.BlockSpec((None, heads, chunk),
                                lambda b, i, h: (b, h, at(i)))
        if kind == _LANES:
            return pl.BlockSpec((1, W), lambda b, i, h: (0, h))
        return pl.BlockSpec((None, None, None) + x.shape[3:],
                            lambda b, i, h: (b, at(i), h, 0, 0))

    return pl.pallas_call(
        kernel, grid=(B, n, HP // W),
        in_specs=[spec(x, k) for x, k in zip(ins, kinds)],
        out_specs=[spec(x, k) for x, k in zip(outs, out_kinds)],
        out_shape=outs, scratch_shapes=scratch,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=name)(*ins)


def _operands(x, dt, G, Bm, Cm, D):
    """The kernels' operands from the operator's: x [B, T, H * P]."""
    B, T, H = dt.shape
    P = x.shape[-1] // H

    def cols(v):
        return jnp.moveaxis(
            v.astype(_F32).reshape(B, T, H // _HEADS, _HEADS), 2, 1)

    return (x, cols(dt), cols(G), jnp.moveaxis(G.astype(_F32), 2, 1), Bm, Cm,
            jnp.repeat(D.astype(_F32), P)[None])


# jitted, so that a model's layers share one trace and one lowering of a
# kernel (its body is unrolled over the heads of a grid step)
@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "residuals"))
def _fused_fwd(x, dt, G, Bm, Cm, D, chunk, interpret, residuals=True):
    """y, and what the backward keeps. x [B, T, H, P], dt, G [B, T, H],
    Bm, Cm [B, T, N], D [H]; T a multiple of `chunk`."""
    B, T, H, P = x.shape
    N, blocks, W = Bm.shape[-1], H // _HEADS, _HEADS * P
    ins = _operands(x.reshape(B, T, H * P), dt, G, Bm, Cm, D)
    sds = jax.ShapeDtypeStruct
    outs, kinds = [sds((B, T, H * P), x.dtype)], [_WIDE]
    if residuals:
        outs.append(sds((B, T // chunk, blocks, N, W), _F32))
        kinds.append(_OWN)
    y, *res = _call(
        functools.partial(_fwd_kernel, P=P), "ssd_chunk_scan", False, ins,
        _INS, outs, kinds, [pltpu.VMEM((blocks, N, W), _F32),
                            pltpu.VMEM((chunk, chunk), _F32)],
        P, chunk, interpret)
    return y.reshape(B, T, H, P), (x, dt, G, Bm, Cm, D, tuple(res))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _fused_bwd(chunk, interpret, saved, dy):
    x, dt, G, Bm, Cm, D, res = saved
    B, T, H, P = x.shape
    N, blocks, W = Bm.shape[-1], H // _HEADS, _HEADS * P
    ins = _operands(x.reshape(B, T, H * P), dt, G, Bm, Cm, D)
    sds = jax.ShapeDtypeStruct
    outs = [sds((B, T, H * P), x.dtype), sds(ins[1].shape, _F32),
            sds(ins[1].shape, _F32), sds((B, T, N), _F32),
            sds((B, T, N), _F32), sds((B, T // chunk, blocks, 8, W), _F32)]
    dx, ddt, dG, dB, dC, dD = _call(
        functools.partial(_bwd_kernel, P=P), "ssd_chunk_scan_bwd", True,
        ins + (dy.reshape(B, T, H * P),) + res, _INS + (_WIDE, _OWN), outs,
        (_WIDE, _COL, _COL, _GROUP, _GROUP, _OWN),
        [pltpu.VMEM((blocks, N, W), _F32),
         pltpu.VMEM((chunk, chunk), _F32), pltpu.VMEM((chunk, chunk), _F32)],
        P, chunk, interpret)

    def rows(v):
        return jnp.moveaxis(v, 1, 2).reshape(B, T, H)

    dD = dD.sum((0, 1, 3)).reshape(H, P).sum(-1)
    return (dx.reshape(B, T, H, P), rows(ddt).astype(dt.dtype),
            rows(dG).astype(G.dtype), dB.astype(Bm.dtype),
            dC.astype(Cm.dtype), dD.astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _fused(x, dt, G, Bm, Cm, D, chunk, interpret=False):
    """The operator through the two kernels; `interpret` runs them in the
    Pallas interpreter (the tests' way, on the CPU)."""
    return _fused_fwd(x, dt, G, Bm, Cm, D, chunk, interpret,
                      residuals=False)[0]


_fused.defvjp(_fused_fwd, _fused_bwd)


def _tiles_ok(H, P, N, chunk):
    """Shapes the kernels' blocks tile: heads that fill or evenly share
    the 128 lanes, eight of them a grid step, a state and a chunk of
    whole lane tiles."""
    return ((P % LANES == 0 or P == LANES // 2) and H % _HEADS == 0
            and N % LANES == 0 and chunk % LANES == 0)


def ssd_chunk_scan(x, dt, G, Bm, Cm, D, *, chunk=256):
    """x [B, T, H, P]; dt [B, T, H] (the step, after its softplus) and
    G = `chunk_cumsum(dt, A, chunk)` [B, T, H], float32; Bm, Cm
    [B, T, N] (one group: every head's); D [H]. Returns y [B, T, H, P]
    in x's dtype, from a zero initial state. T need not divide by
    `chunk`."""
    B, T, H, P = x.shape
    pad = -T % chunk
    if pad:
        # tokens past the end: dt = 0 and a flat G leave the state as it is
        x, dt, Bm, Cm = (_pad_t(v, pad) for v in (x, dt, Bm, Cm))
        G = _pad_t(G, pad, "edge")
    if _on_tpu() and _tiles_ok(H, P, Bm.shape[-1], chunk):
        y = _fused(x, dt, G, Bm, Cm, D, chunk, False)
    else:
        y = _plain(x, dt, G, Bm, Cm, D, chunk)
    return y[:, :T] if pad else y
