"""Moving the live rows of a row buffer: a gather and the scatter-add
that is its transpose.

    gather_rows(src [N, H], idx [R], live)         -> [R, H]
        row r < live is src[idx[r]]; rows past `live` are zeros
    scatter_add_rows(src [R, H], idx [R], live, N) -> [N, H]
        out[t] = the sum of the rows r < live with idx[r] == t, added in
        float32 and rounded once to src's dtype; zeros where none lands

`live` is a traced scalar: the rows past it belong to nobody, what `src`
holds there is never used (it may be anything, NaN included), and what
`idx` holds there is never looked at. Each routine is a
`jax.custom_vjp` whose backward is the other one, so an expert layer's
four row moves (dispatch, combine and their transposes) are these two.

What runs where. The gather is `jnp.take` everywhere: the compiler's
gather of 16,384 rows of 10 KB takes 1.4 ms where its scatter-add of them
took 32.8 (PERF.md section 6, PR 45), and Mosaic refuses a DMA of one row
of a 2-D array, so a kernel could not fetch rows one by one either. The
scatter-add on a TPU, for bf16 rows a multiple of 128 wide, is ONE Pallas
kernel, `moe_scatter_add_rows`, that never goes through the compiler's
colliding scatter:
  * the live rows are put in the order of their targets once a call (a
    stable argsort of R keys, dead rows behind every target, and one
    gather of rows: a copy that dies inside the call);
  * the kernel is output-stationary: a tile of `TILE` targets stays in
    VMEM as a float32 accumulator while the chunks of `CHUNK` ordered
    rows that hold its rows go by, a contiguous range; a chunk is added
    through the MXU as a 0/1 matrix [targets of the tile, rows of the
    chunk] times the chunk, bf16 x bf16 with float32 accumulation: one
    nonzero a column, so every product is exact and a target's rows are
    summed in float32. A chunk starts where chunks start: rows of a
    neighbouring tile in it match no target of this one;
  * the walk is a scalar-prefetched list of (tile, chunk) visits made
    outside from the ordered indices, at most tiles + chunks long
    (`_visits`); a tile nobody lands in is visited once and written as
    zeros; steps behind the last visit repeat its blocks and fetch
    nothing. Chunks past `live` are never fetched.
Everywhere else (the CPU, other dtypes, widths that do not tile) it is
`.at[idx].add` in float32 with the dead rows sent out of bounds and
dropped. The route follows the platform, the dtype and the width alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tpu import LANES
from ._tpu import on_tpu as _on_tpu

__all__ = ["gather_rows", "scatter_add_rows", "route", "TILE", "CHUNK"]

TILE = 256            # targets resident a grid step
CHUNK = 128           # ordered rows a visit: one pass of the MXU's depth
_LANES_A_DOT = 1024    # columns a product: bounds the float32 temporary
_VMEM = 48 * 1024 * 1024
_F32 = jnp.float32


def route(rows: int, n: int, width: int, dtype) -> str:
    """"kernel" where the scatter-add is the Pallas kernel, else "xla":
    on a TPU, bf16 rows of whole lane tiles, whole chunks and tiles, and a
    step's blocks (the float32 tile, two output blocks, two chunks) inside
    the kernel's VMEM limit."""
    step = width * (TILE * (4 + 2 * 2) + CHUNK * 2 * 2)
    ok = (_on_tpu() and jnp.dtype(dtype) == jnp.bfloat16
          and width % LANES == 0 and rows % CHUNK == 0 and n % TILE == 0
          and step <= _VMEM * 3 // 4)
    return "kernel" if ok else "xla"


# -- the plain forms -----------------------------------------------------------

def _gather(src, idx, live):
    valid = jnp.arange(idx.shape[0]) < live
    rows = jnp.take(src, jnp.where(valid, idx, 0), axis=0)
    return jnp.where(valid[:, None], rows, 0)


def _scatter_add_dense(src, idx, live, n):
    valid = jnp.arange(idx.shape[0]) < live
    out = jnp.zeros((n, src.shape[1]), _F32).at[jnp.where(valid, idx, n)].add(
        src.astype(_F32), mode="drop")
    return out.astype(src.dtype)


# -- the kernel ----------------------------------------------------------------

def _visits(idx_s, live, n, tile, chunk):
    """The walk of the kernel over `idx_s` [R], the targets in ascending
    order with the dead rows' keys (n) last: int32 lists (tile, chunk,
    flags) of length tiles + chunks. A tile's visits are side by side,
    its chunks ascending; flags: 1 the chunk holds rows of the tile, 2 the
    tile's first visit, 4 its last."""
    rows = idx_s.shape[0]
    nt, nc = n // tile, rows // chunk
    edges = jnp.searchsorted(idx_s, np.arange(nt + 1, dtype=np.int32) * tile,
                             side="left").astype(jnp.int32)
    lo, hi = edges[:-1], edges[1:]
    full = hi > lo
    # an empty tile looks at (and never uses) a chunk that is live
    c0 = jnp.where(full, lo // chunk,
                   jnp.minimum(lo, jnp.maximum(live - 1, 0)) // chunk)
    count = jnp.where(full, (hi - 1) // chunk - lo // chunk + 1, 1)
    start = jnp.cumsum(count) - count
    v = np.arange(nt + nc, dtype=np.int32)
    t = jnp.clip(jnp.searchsorted(start, v, side="right") - 1, 0, nt - 1)
    t = t.astype(jnp.int32)
    k, last = v - jnp.take(start, t), jnp.take(count, t) - 1
    inside = k <= last
    c = jnp.take(c0, t) + jnp.minimum(k, last)
    flags = (jnp.where(inside & jnp.take(full, t), 1, 0)
             + jnp.where(k == 0, 2, 0) + jnp.where(k == last, 4, 0))
    return t, c.astype(jnp.int32), flags.astype(jnp.int32)


def _scatter_kernel(tile_ref, chunk_ref, flag_ref, idx_ref, src_ref, out_ref,
                    acc_ref, *, tile, lanes):
    v = pl.program_id(0)
    flag = flag_ref[v]
    width = src_ref.shape[1]

    @pl.when(flag & 2 != 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(flag & 1 != 0)
    def add():
        target = tile_ref[v] * tile + jax.lax.broadcasted_iota(
            jnp.int32, (tile, idx_ref.shape[-1]), 0)
        hot = (idx_ref[0] == target).astype(src_ref.dtype)
        for h in range(0, width, lanes):
            cols = slice(h, min(h + lanes, width))
            acc_ref[:, cols] += jnp.dot(hot, src_ref[:, cols],
                                        preferred_element_type=_F32)

    @pl.when(flag & 4 != 0)
    def end():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "interpret", "tile", "chunk"))
def _scatter_add_fused(src, idx, live, n, interpret=False, tile=TILE,
                       chunk=CHUNK):
    """The kernel route; `interpret` runs it in the Pallas interpreter and
    `tile` / `chunk` are smaller there (the tests' way, on the CPU). Jitted:
    a step's calls (two a layer, one shape) are traced and lowered once."""
    rows, width = src.shape
    valid = jnp.arange(rows) < live
    key = jnp.where(valid, idx.astype(jnp.int32), n)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    idx_s = jnp.take(key, perm, mode="clip")
    # a dead place copies row 0, which is live whenever a chunk is used; a
    # permutation is in bounds: no pass over the copy to fill what is not
    ordered = jnp.take(src, jnp.where(valid, perm, 0), axis=0, mode="clip")
    visits = _visits(idx_s, live, n, tile, chunk)
    steps = visits[0].shape[0]
    return pl.pallas_call(
        functools.partial(_scatter_kernel, tile=tile,
                          lanes=min(_LANES_A_DOT, width)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[
                pl.BlockSpec((None, 1, chunk), lambda v, t, c, f: (c[v], 0, 0)),
                pl.BlockSpec((chunk, width), lambda v, t, c, f: (c[v], 0))],
            out_specs=pl.BlockSpec((tile, width), lambda v, t, c, f: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((tile, width), _F32)]),
        out_shape=jax.ShapeDtypeStruct((n, width), src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        name="moe_scatter_add_rows", interpret=interpret,
    )(*visits, idx_s.reshape(rows // chunk, 1, chunk), ordered)


# -- the pair -------------------------------------------------------------------

def _scatter_add(src, idx, live, n):
    if route(src.shape[0], n, src.shape[1], src.dtype) == "kernel":
        return _scatter_add_fused(src, idx, live, n)
    return _scatter_add_dense(src, idx, live, n)


def gather_rows(src, idx, live):
    """src [N, H], idx [R] int32, live [] int32 -> [R, H]: row r < live is
    src[idx[r]], rows past `live` are zeros."""
    return _gather_rows(src.shape[0], src, idx, live)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_rows(n, src, idx, live):
    return _gather(src, idx, live)


def _gather_fwd(n, src, idx, live):
    return _gather(src, idx, live), (idx, live)


def _gather_bwd(n, res, g):
    idx, live = res
    return _scatter_add(g, idx, live, n), None, None


_gather_rows.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def scatter_add_rows(src, idx, live, n):
    """src [R, H], idx [R] int32, live [] int32 -> [n, H] in src's dtype:
    out[t] = the float32 sum of the rows r < live with idx[r] == t."""
    return _scatter_add(src, idx, live, n)


def _scatter_fwd(src, idx, live, n):
    return _scatter_add(src, idx, live, n), (idx, live)


def _scatter_bwd(n, res, g):
    idx, live = res
    return _gather(g, idx, live), None, None


scatter_add_rows.defvjp(_scatter_fwd, _scatter_bwd)
