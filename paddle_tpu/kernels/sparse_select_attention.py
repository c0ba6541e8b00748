"""Attention over a learned selection of keys: an indexer scores every
causal (query, key) pair, each query keeps the `top_k` keys of largest
score, and the heads attend to those alone.

    I[t, s]  = sum_j w[t, j] * relu(qi[t, j] . ki[s])          (s <= t)
    S_t      = the top_k keys s <= t of largest I[t, s]  (all while t < top_k)
    o[t, h]  = sum_{s in S_t} softmax_{s in S_t}(a[t, s, h]) v[s, h]
    a        = (qn . kn + qr . kr) * scale        (kr one row for all heads)
    L_I      = mean_t KL(p_t || softmax_{s in S_t} I[t, s]),
    p[t, s]  = mean over heads of the heads' softmax probability at (t, s)

One sequence a call (the model maps over the batch). The pieces, each a
function of arrays:

  * `index_scores(qi, ki, w)` -> I [S, S] float32, `NEG` where s > t.
    On a TPU one Pallas kernel (`dsa_index_scores`), a (rows, keys) tile a
    grid step, the index heads a loop inside it. The selection is hard: a
    rounding of a score picks another key, so the products are made from
    float32 operands split into two bfloat16 halves (hi.hi + hi.lo +
    lo.hi, float32 accumulation: 2^-16 relative, three MXU passes instead
    of the six of a float32 product).
  * `select_top_k(I, top_k)` -> (mask [S, S] int8, log-sum-exp of I over
    the selected keys [S]). EXACT: the top_k-th largest score of a row is
    found bit by bit (32 counting passes over an order-preserving integer
    image of the float32 scores), equal scores at the cut are taken lowest
    position first (log2 S more passes), so every row keeps exactly
    min(t + 1, top_k) keys. Plain `jax.numpy` in a `fori_loop`: each pass
    is one fused compare-and-count over the scores.
  * `selected_attention(qn, qr, kn, kr, v, mask, scale)` -> (o, lse): the
    heads' attention under the mask, a `jax.custom_vjp` over three Pallas
    kernels on a TPU (`dsa_core_fwd`, `dsa_core_bwd_dq`,
    `dsa_core_bwd_dkv`; online softmax, the no-rope and rope products
    apart so that the 64-wide rope key is read once for all heads). Its
    forward's `o` and `lse` carry the name
    `flash_attention.SPLASH_RESIDUALS`: under `TrainStep`'s remat policy
    the forward runs once a step.
  * `head_prob_sum(qn, qr, kn, kr, lse, mask, scale, acc)` -> acc + the sum
    over these heads of their probabilities [S, S] float32
    (`dsa_head_probs`): the indexer's target, a group of heads a call.
    These four walk the masked [S, S] tile space alike: the grid's tile
    axis runs over a prefetched list of the causal triangle's (row block,
    key block) pairs and no others (`_live_tiles`), and a step takes as
    many of the call's heads as fit a VMEM budget (`_heads_per_step`), a
    loop over them inside the body: the mask tile and the rope key are
    fetched, and the mask widened, once for all of them. Each leaves a
    set-up event `dsa.grid` a trace (`observability/scopes.py`).
  * `indexer_loss(qi, ki, w, scores, mask, lse_i, psum, heads)` -> L_I, a
    `jax.custom_vjp`: its backward is the gradient of L_I with respect to
    the scores, (softmax_S(I) - p) / S on the selected pairs (kept from
    the forward in bfloat16), pulled back to qi, ki and w by two more
    kernels (`dsa_index_bwd_dq`, `dsa_index_bwd_dk`). `scores`, `mask`,
    `lse_i` and `psum` are constants to it.

Everywhere but a TPU (and at sizes that do not tile) every piece is the
same equations in dense `jax.numpy`, and jax's transpose the backward.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tpu import LANES
from ._tpu import on_tpu as _on_tpu
from .flash_attention import SPLASH_RESIDUALS

__all__ = ["index_scores", "select_top_k", "selected_attention",
           "head_prob_sum", "indexer_loss", "NEG"]

_F32 = jnp.float32
_BF16 = jnp.bfloat16
NEG = -1e30
_VMEM = 64 * 1024 * 1024
# of it, what a grid step's blocks (in two buffers) and scratch may take: the
# rest is the body's own [rows, keys] float32 tiles (scores, probabilities,
# their gradients: 1 MB each at 512 x 512) and Mosaic's
_STEP_VMEM = 40 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))           # a @ b.T


def _tiles(S):
    """Whether the kernels' blocks tile a sequence of S (else: dense)."""
    return S % LANES == 0


def _blocks(S, want_q=256, want_k=512):
    """(rows, keys) of a tile: the largest of 512, 256, 128 that is no
    more than wanted and divides S."""
    def pick(want):
        return next(b for b in (512, 256, 128) if b <= want and S % b == 0)
    return pick(want_q), pick(want_k)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM)


def _diag(i, bq, bk):
    """Last key block a block of query rows sees."""
    return (i * bq + bq - 1) // bk


def _causal(i, j, bq, bk):
    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return cols <= rows


# -- index scores --------------------------------------------------------------

def _split(x):
    """float32 x as two bfloat16 halves, hi + lo = x to 2^-17. The
    roundings are `reduce_precision`, which XLA keeps: a plain
    float32 -> bfloat16 -> float32 round trip is dropped where producer
    and consumer fuse (excess precision), lo becomes x - x = 0 inside a
    jitted step, and the scores silently fall to one bfloat16 pass (found
    on the chip, PR 33: ~6 other keys a row)."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(_BF16), lo.astype(_BF16)


def _index_scores_dense(qi, ki, w):
    d = jnp.einsum("tjd,sd->tjs", qi.astype(_F32), ki.astype(_F32),
                   precision=jax.lax.Precision.HIGHEST)
    sc = jnp.einsum("tjs,tj->ts", jax.nn.relu(d), w.astype(_F32),
                    precision=jax.lax.Precision.HIGHEST)
    S = sc.shape[0]
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, NEG)


def _index_fwd_kernel(qh_ref, ql_ref, kh_ref, kl_ref, w_ref, o_ref, *, J, bq,
                      bk):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j <= _diag(i, bq, bk))
    def run():
        kh, kl = kh_ref[...], kl_ref[...]
        acc = jnp.zeros((bq, bk), _F32)
        for h in range(J):
            qh = qh_ref[h]
            d = (jax.lax.dot_general(qh, kh, _NT, preferred_element_type=_F32)
                 + jax.lax.dot_general(qh, kl, _NT,
                                       preferred_element_type=_F32)
                 + jax.lax.dot_general(ql_ref[h], kh, _NT,
                                       preferred_element_type=_F32))
            acc = acc + w_ref[:, h:h + 1] * jnp.maximum(d, 0.0)
        o_ref[...] = jnp.where(_causal(i, j, bq, bk), acc, NEG)

    @pl.when(j > _diag(i, bq, bk))
    def skip():
        o_ref[...] = jnp.full((bq, bk), NEG, _F32)


def _index_scores_fused(qi, ki, w, interpret=False):
    S, J, D = qi.shape
    bq, bk = _blocks(S, 128, 512)
    qh, ql = _split(jnp.swapaxes(qi.astype(_F32), 0, 1))     # [J, S, D]
    kh, kl = _split(ki.astype(_F32))

    def kmap(i, j):
        return jnp.minimum(j, _diag(i, bq, bk)), 0

    q_spec = pl.BlockSpec((J, bq, D), lambda i, j: (0, i, 0))
    k_spec = pl.BlockSpec((bk, D), kmap)
    return pl.pallas_call(
        functools.partial(_index_fwd_kernel, J=J, bq=bq, bk=bk),
        grid=(S // bq, S // bk),
        in_specs=[q_spec, q_spec, k_spec, k_spec,
                  pl.BlockSpec((bq, J), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((S, S), _F32),
        compiler_params=_params("parallel", "arbitrary"),
        name="dsa_index_scores", interpret=interpret,
    )(qh, ql, kh, kl, w.astype(_F32))


def index_scores(qi, ki, w, use_pallas=None):
    """qi [S, J, D], ki [S, D], w [S, J] -> I [S, S] float32, NEG above the
    diagonal."""
    if use_pallas is None:
        use_pallas = _on_tpu() and _tiles(qi.shape[0])
    if use_pallas:
        return _index_scores_fused(qi, ki, w)
    return _index_scores_dense(qi, ki, w)


# -- the selection -------------------------------------------------------------

def _ordered(scores):
    """uint32 image of float32 scores with the same order."""
    bits = jax.lax.bitcast_convert_type(scores.astype(_F32), jnp.uint32)
    neg = (bits >> 31).astype(bool)
    return jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))


def _select_rows(scores, first, top_k):
    """select_top_k for the rows [first, first + R) of the sequence:
    scores [R, S]."""
    R, S = scores.shape
    rows = first + jnp.arange(R, dtype=jnp.int32)
    cols = jnp.arange(S, dtype=jnp.int32)[None, :]
    seen = cols <= rows[:, None]
    want = jnp.minimum(rows + 1, top_k)

    def image():
        # made again in every pass (one fused read of the scores), so that
        # no second array of the scores' size lives through the loop; 0
        # where s > t, under every candidate
        return jnp.where(seen, _ordered(scores), jnp.uint32(0))

    def value_bit(b, thr):
        cand = thr | (jnp.uint32(1) << (31 - b).astype(jnp.uint32))
        n = jnp.sum(image() >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(n >= want, cand, thr)

    thr = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((R,), jnp.uint32))
    u = image()
    above = (u > thr[:, None]) & seen
    at = (u == thr[:, None]) & seen
    left = want - jnp.sum(above, axis=1, dtype=jnp.int32)
    n_bits = max(1, math.ceil(math.log2(S)))

    def place_bit(b, p):
        cand = p | (jnp.int32(1) << (n_bits - 1 - b))
        n = jnp.sum(at & (cols < cand[:, None]), axis=1, dtype=jnp.int32)
        return jnp.where(n < left, cand, p)

    cut = jax.lax.fori_loop(0, n_bits, place_bit, jnp.zeros((R,), jnp.int32))
    keep = above | (at & (cols <= cut[:, None]))
    top = jnp.max(jnp.where(keep, scores, NEG), axis=1)
    lse = top + jnp.log(jnp.sum(
        jnp.where(keep, jnp.exp(scores - top[:, None]), 0.0), axis=1))
    return keep.astype(jnp.int8), lse


def select_top_k(scores, top_k, block_rows=2048):
    """scores [S, S] float32 (NEG above the diagonal) -> (mask [S, S] int8
    with exactly min(t + 1, top_k) ones in row t, all at s <= t;
    log-sum-exp of the scores over a row's selected keys [S] float32).
    `block_rows` rows at a time (a row is selected alone)."""
    S = scores.shape[0]
    if S % block_rows or S == block_rows:
        return _select_rows(scores, 0, top_k)
    n = S // block_rows
    mask, lse = jax.lax.map(
        lambda a: _select_rows(a[0], a[1], top_k),
        (scores.reshape(n, block_rows, S),
         jnp.arange(n, dtype=jnp.int32) * block_rows))
    return mask.reshape(S, S), lse.reshape(S)


# -- the heads' attention under the mask --------------------------------------

def _scores_of(qn, qr, kn, kr, keep, scale):
    s = (jax.lax.dot_general(qn, kn, _NT, preferred_element_type=_F32)
         + jax.lax.dot_general(qr, kr, _NT, preferred_element_type=_F32))
    return jnp.where(keep, s * scale, NEG)


def _core_dense(qn, qr, kn, kr, v, mask, scale):
    f = lambda a: a.astype(_F32)
    s = (jnp.einsum("htd,hsd->hts", f(qn), f(kn))
         + jnp.einsum("htd,sd->hts", f(qr), f(kr))) * scale
    s = jnp.where(mask[None] != 0, s, NEG)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum("hts,hsd->htd", p, f(v)).astype(v.dtype), lse


def _live_tiles(S, bq, bk, by_keys=False):
    """The (row block, key block) pairs of the causal triangle, as two
    int32 lists the kernels' tile axis walks: a row block's tiles side by
    side, key blocks ascending, or (`by_keys`) a key block's, row blocks
    ascending. From S and the blocks alone: with top_k keys of a row kept
    anywhere below the diagonal no tile of the triangle is empty."""
    nq, nk = S // bq, S // bk
    if by_keys:
        pairs = [(i, j) for j in range(nk) for i in range(j * bk // bq, nq)]
    else:
        pairs = [(i, j) for i in range(nq)
                 for j in range(_diag(i, bq, bk) + 1)]
    rows, keys = zip(*pairs)
    return np.asarray(rows, np.int32), np.asarray(keys, np.int32)


def _vmem_bytes(shape, dtype):
    """Bytes of one VMEM buffer of a block: its last two dimensions in
    the dtype's tiles of (8 x 4 / itemsize, 128)."""
    item = jnp.dtype(dtype).itemsize
    dims = [1, 1] + [d or 1 for d in shape]
    pad = lambda n, m: -(-n // m) * m
    return (math.prod(dims[:-2]) * pad(dims[-2], 32 // item)
            * pad(dims[-1], LANES) * item)


def _heads_per_step(H, step_bytes):
    """Heads a grid step takes: the most that divide H and whose step,
    `step_bytes(heads)`, fits `_STEP_VMEM`."""
    return max(g for g in range(1, H + 1)
               if H % g == 0 and (g == 1 or step_bytes(g) <= _STEP_VMEM))


def _tile_maps(heads_inner=False):
    """Index maps for a grid (head group, tile), or (tile, head group),
    whose tile axis walks the prefetched lists: of the heads' row-side
    blocks, their key-side blocks, the shared rope key, the mask tile and
    the heads' row vectors [G, 1, rows]."""
    def at(f):
        if heads_inner:
            return lambda t, h, rows, keys: f(h, rows[t], keys[t])
        return lambda h, t, rows, keys: f(h, rows[t], keys[t])
    return (at(lambda h, i, j: (h, i, 0)), at(lambda h, i, j: (h, j, 0)),
            at(lambda h, i, j: (j, 0)), at(lambda h, i, j: (i, j)),
            at(lambda h, i, j: (h, 0, i)))


def _walk(kernel, name, tiles, blocks, H, specs, args, interpret, heads=None,
          heads_inner=False, aliases=None):
    """One of the four kernels over its live tiles, several heads a step:
    `specs(G)` gives (in_specs, out_specs, out_shape, scratch_shapes) for
    G heads a step, G the most whose blocks fit (`heads`: the tests'),
    and the set-up event `dsa.grid` says what grid that made."""
    from ..observability import spans

    def step_bytes(G):
        ins, outs, shapes, scratch = specs(G)
        moved = ([(sp.block_shape, a.dtype) for sp, a in zip(ins, args)]
                 + [(sp.block_shape, o.dtype) for sp, o in zip(outs, shapes)])
        return (2 * sum(_vmem_bytes(*b) for b in moved)
                + sum(_vmem_bytes(m.shape, m.dtype) for m in scratch))

    G = heads or _heads_per_step(H, step_bytes)
    in_specs, out_specs, out_shape, scratch = specs(G)
    live = len(tiles[0])
    grid = (live, H // G) if heads_inner else (H // G, live)
    spans.setup_event("dsa.grid", kernel=name, grid_steps=math.prod(grid),
                      live_tiles=live, heads_per_step=G, rows=blocks[0],
                      keys=blocks[1])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, input_output_aliases=aliases or {},
        compiler_params=_params("parallel", "arbitrary"),
        name=name, interpret=interpret,
    )(*tiles, *args)


def _each_head(ref, body):
    """body(g) for the heads of a step's blocks, in ascending order."""
    def turn(g, carry):
        body(g)
        return carry
    jax.lax.fori_loop(0, ref.shape[0], turn, 0)


def _core_fwd_kernel(rows_ref, keys_ref, qn_ref, qr_ref, kn_ref, kr_ref,
                     v_ref, mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                     *, scale, bq, bk):
    t = pl.program_id(1)
    i, j = rows_ref[t], keys_ref[t]

    @pl.when(j == 0)
    def init():
        m_scr[...] = jnp.full(m_scr.shape, NEG, _F32)
        l_scr[...] = jnp.zeros(l_scr.shape, _F32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, _F32)

    keep = mask_ref[...].astype(jnp.int32) != 0
    kr = kr_ref[...]

    def run(g):
        s = _scores_of(qn_ref[g], qr_ref[g], kn_ref[g], kr, keep, scale)
        m_prev = m_scr[g]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_next[:, :1])
        alpha = jnp.exp(m_prev - m_next)
        l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[g] = m_next
        acc_scr[g] = alpha[:, :1] * acc_scr[g] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[g], preferred_element_type=_F32)

    _each_head(qn_ref, run)

    @pl.when(j == _diag(i, bq, bk))
    def end():
        def write(g):
            l = l_scr[g]
            o_ref[g] = (acc_scr[g] / l[:, :1]).astype(o_ref.dtype)
            lse_ref[g] = m_scr[g] + jnp.log(l)

        _each_head(qn_ref, write)


def _core_fwd_fused(qn, qr, kn, kr, v, mask, scale, interpret=False,
                    heads=None, rows=512):
    """`heads` and `rows` (here and below) are the tests': a given number
    of heads a step, the 256 rows a step of the grid before."""
    H, S, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    bq, bk = _blocks(S, rows, 512)
    q_row, k_row, shared, tile, _ = _tile_maps()

    def specs(G):
        return ([pl.BlockSpec((G, bq, dn), q_row),
                 pl.BlockSpec((G, bq, dr), q_row),
                 pl.BlockSpec((G, bk, dn), k_row),
                 pl.BlockSpec((bk, dr), shared),
                 pl.BlockSpec((G, bk, dv), k_row),
                 pl.BlockSpec((bq, bk), tile)],
                [pl.BlockSpec((G, bq, dv), q_row),
                 pl.BlockSpec((G, bq, LANES), q_row)],
                [jax.ShapeDtypeStruct((H, S, dv), v.dtype),
                 jax.ShapeDtypeStruct((H, S, LANES), _F32)],
                [pltpu.VMEM((G, bq, LANES), _F32),
                 pltpu.VMEM((G, bq, LANES), _F32),
                 pltpu.VMEM((G, bq, dv), _F32)])

    o, lse = _walk(
        functools.partial(_core_fwd_kernel, scale=scale, bq=bq, bk=bk),
        "dsa_core_fwd", _live_tiles(S, bq, bk), (bq, bk), H, specs,
        (qn, qr, kn, kr, v, mask), interpret, heads)
    return o, lse[..., 0]


def _p_and_ds(g, qn_ref, qr_ref, kn_ref, kr, v_ref, keep, lse_ref, do_ref,
              di_ref, scale):
    s = _scores_of(qn_ref[g], qr_ref[g], kn_ref[g], kr, keep, scale)
    p = jnp.exp(s - jnp.expand_dims(lse_ref[g, 0], -1))
    dp = jax.lax.dot_general(do_ref[g], v_ref[g], _NT,
                             preferred_element_type=_F32)
    ds = p * (dp - jnp.expand_dims(di_ref[g, 0], -1)) * scale
    return p, ds


def _core_dq_kernel(rows_ref, keys_ref, qn_ref, qr_ref, kn_ref, kr_ref,
                    v_ref, mask_ref, lse_ref, do_ref, di_ref, dqn_ref,
                    dqr_ref, dqn_scr, dqr_scr, *, scale, bq, bk):
    t = pl.program_id(1)
    i, j = rows_ref[t], keys_ref[t]

    @pl.when(j == 0)
    def init():
        dqn_scr[...] = jnp.zeros(dqn_scr.shape, _F32)
        dqr_scr[...] = jnp.zeros(dqr_scr.shape, _F32)

    keep = mask_ref[...].astype(jnp.int32) != 0
    kr = kr_ref[...]

    def run(g):
        _, ds = _p_and_ds(g, qn_ref, qr_ref, kn_ref, kr, v_ref, keep,
                          lse_ref, do_ref, di_ref, scale)
        ds = ds.astype(kn_ref.dtype)
        dqn_scr[g] += jnp.dot(ds, kn_ref[g], preferred_element_type=_F32)
        dqr_scr[g] += jnp.dot(ds, kr, preferred_element_type=_F32)

    _each_head(qn_ref, run)

    @pl.when(j == _diag(i, bq, bk))
    def end():
        dqn_ref[...] = dqn_scr[...].astype(dqn_ref.dtype)
        dqr_ref[...] = dqr_scr[...].astype(dqr_ref.dtype)


def _core_dkv_kernel(rows_ref, keys_ref, qn_ref, qr_ref, kn_ref, kr_ref,
                     v_ref, mask_ref, lse_ref, do_ref, di_ref, dkn_ref,
                     dv_ref, dkr_ref, dkn_scr, dv_scr, dkr_scr, *, scale, bq,
                     bk, nq):
    t = pl.program_id(1)
    i, j = rows_ref[t], keys_ref[t]

    @pl.when(i == (j * bk) // bq)
    def init():
        dkn_scr[...] = jnp.zeros(dkn_scr.shape, _F32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, _F32)
        dkr_scr[...] = jnp.zeros(dkr_scr.shape, _F32)

    keep = mask_ref[...].astype(jnp.int32) != 0
    kr = kr_ref[...]

    def run(g):
        p, ds = _p_and_ds(g, qn_ref, qr_ref, kn_ref, kr, v_ref, keep,
                          lse_ref, do_ref, di_ref, scale)
        dv_scr[g] += jnp.dot(p.T.astype(do_ref.dtype), do_ref[g],
                             preferred_element_type=_F32)
        dst = ds.T.astype(qn_ref.dtype)
        dkn_scr[g] += jnp.dot(dst, qn_ref[g], preferred_element_type=_F32)
        dkr_scr[...] += jnp.dot(dst, qr_ref[g], preferred_element_type=_F32)

    _each_head(qn_ref, run)

    @pl.when(i == nq - 1)
    def end():
        dkn_ref[...] = dkn_scr[...].astype(dkn_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)
        dkr_ref[...] = dkr_scr[...]


def _core_bwd_fused(qn, qr, kn, kr, v, mask, o, lse, do, scale,
                    interpret=False, heads=None, rows=512):
    H, S, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    di = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1)[:, None, :]
    args = (qn, qr, kn, kr, v, mask, lse[:, None, :], do, di)
    q_row, k_row, shared, tile, vec = _tile_maps()

    def in_specs(G, bq, bk):
        return [pl.BlockSpec((G, bq, dn), q_row),
                pl.BlockSpec((G, bq, dr), q_row),
                pl.BlockSpec((G, bk, dn), k_row),
                pl.BlockSpec((bk, dr), shared),
                pl.BlockSpec((G, bk, dv), k_row),
                pl.BlockSpec((bq, bk), tile),
                pl.BlockSpec((G, 1, bq), vec),
                pl.BlockSpec((G, bq, dv), q_row),
                pl.BlockSpec((G, 1, bq), vec)]

    bq, bk = _blocks(S, rows, 512)
    dqn, dqr = _walk(
        functools.partial(_core_dq_kernel, scale=scale, bq=bq, bk=bk),
        "dsa_core_bwd_dq", _live_tiles(S, bq, bk), (bq, bk), H,
        lambda G: (in_specs(G, bq, bk),
                   [pl.BlockSpec((G, bq, dn), q_row),
                    pl.BlockSpec((G, bq, dr), q_row)],
                   [jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                    jax.ShapeDtypeStruct(qr.shape, qr.dtype)],
                   [pltpu.VMEM((G, bq, dn), _F32),
                    pltpu.VMEM((G, bq, dr), _F32)]),
        args, interpret, heads)

    # a key block's row blocks side by side, 256 rows as before; the rope
    # key's gradient sums over the heads of a step inside the kernel, and
    # over the steps' groups of heads here
    bq, bk = _blocks(S, 256, 512)
    dkn, dvv, dkr = _walk(
        functools.partial(_core_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          nq=S // bq),
        "dsa_core_bwd_dkv", _live_tiles(S, bq, bk, by_keys=True), (bq, bk),
        H,
        lambda G: (in_specs(G, bq, bk),
                   [pl.BlockSpec((G, bk, dn), k_row),
                    pl.BlockSpec((G, bk, dv), k_row),
                    pl.BlockSpec((None, bk, dr), k_row)],
                   [jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                    jax.ShapeDtypeStruct(v.shape, v.dtype),
                    jax.ShapeDtypeStruct((H // G,) + kr.shape, _F32)],
                   [pltpu.VMEM((G, bk, dn), _F32),
                    pltpu.VMEM((G, bk, dv), _F32),
                    pltpu.VMEM((bk, dr), _F32)]),
        args, interpret, heads)
    return dqn, dqr, dkn, jnp.sum(dkr, axis=0).astype(kr.dtype), dvv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _core(qn, qr, kn, kr, v, mask, scale, interpret):
    return _core_fwd_fused(qn, qr, kn, kr, v, mask, scale, interpret)


def _core_fwd(qn, qr, kn, kr, v, mask, scale, interpret):
    o, lse = _core_fwd_fused(qn, qr, kn, kr, v, mask, scale, interpret)
    o = checkpoint_name(o, SPLASH_RESIDUALS)
    lse = checkpoint_name(lse, SPLASH_RESIDUALS)
    return (o, lse), (qn, qr, kn, kr, v, mask, o, lse)


def _core_bwd(scale, interpret, res, cts):
    qn, qr, kn, kr, v, mask, o, lse = res
    do = cts[0]                     # lse is read under stop_gradient alone
    dqn, dqr, dkn, dkr, dv = _core_bwd_fused(
        qn, qr, kn, kr, v, mask, o, lse, do.astype(v.dtype), scale,
        interpret)
    return dqn, dqr, dkn, dkr, dv, None


_core.defvjp(_core_fwd, _core_bwd)


def selected_attention(qn, qr, kn, kr, v, mask, scale, use_pallas=None):
    """qn, kn [H, S, dn], qr [H, S, dr], kr [S, dr], v [H, S, dv], mask
    [S, S] int8 -> (o [H, S, dv] in v's dtype, lse [H, S] float32)."""
    if use_pallas is None:
        use_pallas = _on_tpu() and _tiles(qn.shape[1])
    if use_pallas:
        return _core(qn, qr, kn, kr, v, mask, float(scale), False)
    return _core_dense(qn, qr, kn, kr, v, mask, scale)


# -- the indexer's target ------------------------------------------------------

def _head_probs_dense(qn, qr, kn, kr, lse, mask, scale, acc):
    f = lambda a: a.astype(_F32)
    s = (jnp.einsum("htd,hsd->hts", f(qn), f(kn))
         + jnp.einsum("htd,sd->hts", f(qr), f(kr))) * scale
    p = jnp.where(mask[None] != 0, jnp.exp(s - lse[..., None]), 0.0)
    return jnp.sum(p, axis=0) + (0.0 if acc is None else acc)


def _head_probs_kernel(rows_ref, keys_ref, qn_ref, qr_ref, kn_ref, kr_ref,
                       lse_ref, mask_ref, *rest, scale):
    acc_ref = rest[0] if len(rest) == 3 else None
    o_ref, scr = rest[-2:]
    h = pl.program_id(1)

    @pl.when(h == 0)
    def init():
        scr[...] = (jnp.zeros(scr.shape, _F32) if acc_ref is None
                    else acc_ref[...])

    keep = mask_ref[...].astype(jnp.int32) != 0
    kr = kr_ref[...]

    def run(g):
        s = _scores_of(qn_ref[g], qr_ref[g], kn_ref[g], kr, keep, scale)
        scr[...] += jnp.exp(s - jnp.expand_dims(lse_ref[g, 0], -1))

    _each_head(qn_ref, run)

    @pl.when(h == pl.num_programs(1) - 1)
    def end():
        o_ref[...] = scr[...]


def _head_probs_fused(qn, qr, kn, kr, lse, mask, scale, acc,
                      interpret=False, heads=None, rows=512):
    H, S, dn = qn.shape
    dr = qr.shape[-1]
    bq, bk = _blocks(S, rows, 512)
    q_row, k_row, shared, tile, vec = _tile_maps(heads_inner=True)
    tile = pl.BlockSpec((bq, bk), tile)
    more = () if acc is None else (acc,)        # None: start from nothing
    [out] = _walk(
        functools.partial(_head_probs_kernel, scale=scale),
        "dsa_head_probs", _live_tiles(S, bq, bk), (bq, bk), H,
        lambda G: ([pl.BlockSpec((G, bq, dn), q_row),
                    pl.BlockSpec((G, bq, dr), q_row),
                    pl.BlockSpec((G, bk, dn), k_row),
                    pl.BlockSpec((bk, dr), shared),
                    pl.BlockSpec((G, 1, bq), vec),
                    tile] + [tile] * len(more),
                   [tile], [jax.ShapeDtypeStruct((S, S), _F32)],
                   [pltpu.VMEM((bq, bk), _F32)]),
        (qn, qr, kn, kr, lse[:, None, :], mask) + more, interpret, heads,
        heads_inner=True, aliases={8: 0} if more else None)
    # tiles above the diagonal are not walked: they keep acc's zeros, and
    # without acc nothing was written there
    return out if more else jnp.tril(out)


def head_prob_sum(qn, qr, kn, kr, lse, mask, scale, acc, use_pallas=None):
    """acc [S, S] float32 (None: nothing yet) + the sum over these heads of
    exp(a - lse) on the selected pairs."""
    if use_pallas is None:
        use_pallas = _on_tpu() and _tiles(qn.shape[1])
    if use_pallas:
        return _head_probs_fused(qn, qr, kn, kr, lse, mask, float(scale),
                                 acc)
    return _head_probs_dense(qn, qr, kn, kr, lse, mask, scale, acc)


# -- the indexer's loss --------------------------------------------------------

def _kl_and_grad(scores, mask, lse_i, psum, heads):
    """(L_I, dL_I / dscores in bfloat16) of one sequence."""
    S = scores.shape[0]
    keep = mask != 0
    p = psum * (1.0 / heads)
    logq = scores - lse_i[:, None]
    safe = jnp.where(p > 0, p, 1.0)
    kl = jnp.sum(jnp.where(keep & (p > 0), p * (jnp.log(safe) - logq), 0.0))
    grad = jnp.where(keep, (jnp.exp(logq) - p) * (1.0 / S), 0.0)
    return kl / S, grad.astype(_BF16)


def _index_bwd_dense(g, qi, ki, w):
    qf, kf, wf = qi.astype(_F32), ki.astype(_F32), w.astype(_F32)
    d = jnp.einsum("tjd,sd->tjs", qf, kf)
    gf = g.astype(_F32)
    dw = jnp.einsum("ts,tjs->tj", gf, jax.nn.relu(d))
    dd = gf[:, None, :] * wf[:, :, None] * (d > 0)
    return (jnp.einsum("tjs,sd->tjd", dd, kf),
            jnp.einsum("tjs,tjd->sd", dd, qf), dw)


def _index_dq_kernel(g_ref, q_ref, k_ref, w_ref, dq_ref, dw_ref, dq_scr,
                     dw_scr, *, J, bq, bk, nk):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, _F32)
        dw_scr[...] = jnp.zeros(dw_scr.shape, _F32)

    @pl.when(j <= _diag(i, bq, bk))
    def run():
        g = g_ref[...].astype(_F32)
        k = k_ref[...]
        for h in range(J):
            d = jax.lax.dot_general(q_ref[h], k, _NT,
                                    preferred_element_type=_F32)
            dw_scr[:, h:h + 1] += jnp.sum(g * jnp.maximum(d, 0.0), axis=1,
                                          keepdims=True)
            dd = jnp.where(d > 0, g * w_ref[:, h:h + 1], 0.0).astype(k.dtype)
            dq_scr[h] += jnp.dot(dd, k, preferred_element_type=_F32)

    @pl.when(j == nk - 1)
    def end():
        dq_ref[...] = dq_scr[...]
        dw_ref[...] = dw_scr[...]


def _index_dk_kernel(g_ref, q_ref, k_ref, w_ref, dk_ref, dk_scr, *, J, bq, bk,
                     nq):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, _F32)

    @pl.when(j <= _diag(i, bq, bk))
    def run():
        g = g_ref[...].astype(_F32)
        k = k_ref[...]
        for h in range(J):
            q = q_ref[h]
            d = jax.lax.dot_general(q, k, _NT, preferred_element_type=_F32)
            dd = jnp.where(d > 0, g * w_ref[:, h:h + 1], 0.0)
            dk_scr[...] += jnp.dot(dd.T.astype(q.dtype), q,
                                   preferred_element_type=_F32)

    @pl.when(i == nq - 1)
    def end():
        dk_ref[...] = dk_scr[...]


def _index_bwd_fused(g, qi, ki, w, interpret=False):
    S, J, D = qi.shape
    bq, bk = _blocks(S, 128, 512)
    nq, nk = S // bq, S // bk
    q = jnp.swapaxes(qi, 0, 1).astype(_BF16)             # [J, S, D]
    k = ki.astype(_BF16)
    w = w.astype(_F32)

    def last(i, j):
        return jnp.minimum(j, _diag(i, bq, bk))

    dq, dw = pl.pallas_call(
        functools.partial(_index_dq_kernel, J=J, bq=bq, bk=bk, nk=nk),
        grid=(nq, nk),
        in_specs=[pl.BlockSpec((bq, bk), lambda i, j: (i, last(i, j))),
                  pl.BlockSpec((J, bq, D), lambda i, j: (0, i, 0)),
                  pl.BlockSpec((bk, D), lambda i, j: (last(i, j), 0)),
                  pl.BlockSpec((bq, J), lambda i, j: (i, 0))],
        out_specs=[pl.BlockSpec((J, bq, D), lambda i, j: (0, i, 0)),
                   pl.BlockSpec((bq, J), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((J, S, D), _F32),
                   jax.ShapeDtypeStruct((S, J), _F32)],
        scratch_shapes=[pltpu.VMEM((J, bq, D), _F32),
                        pltpu.VMEM((bq, J), _F32)],
        compiler_params=_params("parallel", "arbitrary"),
        name="dsa_index_bwd_dq", interpret=interpret,
    )(g, q, k, w)

    def first(j, i):
        return jnp.maximum(i, (j * bk) // bq)

    dk = pl.pallas_call(
        functools.partial(_index_dk_kernel, J=J, bq=bq, bk=bk, nq=nq),
        grid=(nk, nq),
        in_specs=[pl.BlockSpec((bq, bk), lambda j, i: (first(j, i), j)),
                  pl.BlockSpec((J, bq, D), lambda j, i: (0, first(j, i), 0)),
                  pl.BlockSpec((bk, D), lambda j, i: (j, 0)),
                  pl.BlockSpec((bq, J), lambda j, i: (first(j, i), 0))],
        out_specs=pl.BlockSpec((bk, D), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((S, D), _F32),
        scratch_shapes=[pltpu.VMEM((bk, D), _F32)],
        compiler_params=_params("parallel", "arbitrary"),
        name="dsa_index_bwd_dk", interpret=interpret,
    )(g, q, k, w)
    return jnp.swapaxes(dq, 0, 1), dk, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _indexer_loss(qi, ki, w, scores, mask, lse_i, psum, heads, fused):
    return _kl_and_grad(scores, mask, lse_i, psum, heads)[0]


def _indexer_loss_fwd(qi, ki, w, scores, mask, lse_i, psum, heads, fused):
    loss, g = _kl_and_grad(scores, mask, lse_i, psum, heads)
    return loss, (g, qi.astype(_BF16) if fused else qi, ki, w)


def _indexer_loss_bwd(heads, fused, res, ct):
    g, qi, ki, w = res
    bwd = _index_bwd_fused if fused else _index_bwd_dense
    dq, dk, dw = bwd(g, qi, ki, w)
    ct = ct.astype(_F32)
    return dq * ct, dk * ct, dw * ct, None, None, None, None


_indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


def indexer_loss(qi, ki, w, scores, mask, lse_i, psum, heads,
                 use_pallas=None):
    """L_I of one sequence. qi [S, J, D], ki [S, D], w [S, J] carry the
    gradient; scores = index_scores(qi, ki, w), mask, lse_i =
    select_top_k(scores), psum the heads' summed probabilities and
    `heads` their number are constants."""
    if use_pallas is None:
        use_pallas = _on_tpu() and _tiles(qi.shape[0])
    sg = jax.lax.stop_gradient
    return _indexer_loss(qi.astype(_F32), ki.astype(_F32), w.astype(_F32),
                         sg(scores), mask, sg(lse_i), sg(psum), int(heads),
                         bool(use_pallas))
