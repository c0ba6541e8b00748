"""Ragged paged attention — mixed prefill-chunk + decode rows in ONE
kernel invocation over the paged KV pool (ref: "Ragged Paged Attention",
arxiv 2604.15464 — the TPU-native kernel behind chunked-prefill
continuous batching; the reference's serving analog is
block_multihead_attention's mixed-phase decode driven by
analysis_predictor Run).

Contract: queries arrive PACKED — `q [total_q_tokens, nh, d]` holds every
sequence's rows back to back; per-sequence row metadata
`(q_start, q_len, kv_len)` (i32[num_seqs]) says which rows belong to
sequence s (rows q_start[s] .. q_start[s]+q_len[s]) and how many KV
tokens the sequence holds AFTER this step's keys were scattered into the
pool. A decode row is simply q_len == 1; a prefill chunk is q_len > 1;
an idle slot is q_len == 0. Row t of sequence s sits at absolute
position kv_len[s] - q_len[s] + (t - q_start[s]) and attends causally to
KV positions <= its own, gathered through the per-sequence block table
`page_table` (i32[num_seqs, pages_per_seq]) into the shared
`[kvh, n_pages, page, d]` page pool (page 0 is the engine's scratch
page; unused table entries are 0).

Two routes, same contract (the block_attention.py discipline):
  * a Pallas kernel — per-sequence q blocks stream KV one PAGE at a time
    through VMEM with the online-softmax accumulator idiom from
    block_attention.py; the per-sequence page gather rides the
    PrefetchScalarGridSpec index map (the ragged-index idiom of the
    in-tree paged_attention kernel), so the kernel never materializes a
    dense per-sequence cache;
  * an exact jnp fallback (CPU / unaligned shapes).
Tests flip `_FORCE_PALLAS` to drive the Pallas path through the
interpreter on CPU; production dispatch requires a real TPU.
Block sizes come from kernels/autotune.py (key "ragged_paged_attn").
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ._tpu import on_tpu as _on_tpu

__all__ = ["ragged_paged_attention", "supported"]

_NEG = -1e30
# tests flip this to exercise the Pallas path through the interpreter on
# CPU (interpret mode is orders of magnitude slower than the fallback)
_FORCE_PALLAS = False


def supported(q_shape, pages_shape) -> bool:
    """q: [T, nh, d]; pages: [kvh, n_pages, page, d] — Mosaic-alignment
    gate for the compiled route (the fallback handles everything)."""
    T, nh, d = q_shape
    kvh, _, page, d2 = pages_shape
    return (d == d2 and d % 64 == 0 and page % 8 == 0 and nh % kvh == 0)


def _block_q(total_q: int) -> int:
    """q-block rows per grid step: autotune winner for this packed-size
    class when recorded (kernels/autotune.py), else the largest
    power-of-two block <= min(total_q rounded up, 128). Any value works —
    q is padded up to a block multiple — so the sweep is free to explore."""
    from . import autotune
    hit = autotune.lookup(autotune.cache_key("ragged_paged_attn",
                                             T=_size_class(total_q)))
    if hit:
        b = int(hit[0] if isinstance(hit, (list, tuple)) else hit)
        if b > 0 and (b & (b - 1)) == 0:
            return b
    return min(128, _size_class(total_q))


def _size_class(total_q: int) -> int:
    """Quantize the packed row count to a power of two so one autotune
    sweep covers one (kernel, size-class, device) point."""
    c = 8
    while c < total_q:
        c *= 2
    return c


def _row_ids(T, q_start, q_len):
    """Packed-row bookkeeping shared by both routes: for each row t,
    (sequence id, local index within the sequence, membership bool)."""
    t = jnp.arange(T)
    member = ((t[:, None] >= q_start[None, :])
              & (t[:, None] < (q_start + q_len)[None, :]))
    sid = jnp.argmax(member, axis=1).astype(jnp.int32)
    valid = jnp.any(member, axis=1)
    local = t - q_start[sid]
    return sid, local, valid


def ragged_paged_attention(q, k_pages, v_pages, q_start, q_len, kv_len,
                           page_table, scale=None, use_pallas=None,
                           block_q=None):
    """Packed ragged causal attention over the paged KV pool.

    q: [T, nh, d] packed rows; k/v_pages: [kvh, n_pages, page, d];
    q_start/q_len/kv_len: i32[num_seqs]; page_table:
    i32[num_seqs, pages_per_seq]. Returns [T, nh, d] in q.dtype (f32
    math); rows belonging to no sequence come back zero.
    use_pallas: None = auto (real TPU + aligned, or _FORCE_PALLAS via
    the interpreter), True/False forces the route; block_q overrides the
    autotuned q-block (the sweep's candidate lever)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        use_pallas = (supported(q.shape, k_pages.shape)
                      and (_on_tpu() or _FORCE_PALLAS))
    elif use_pallas and not supported(q.shape, k_pages.shape):
        # an EXPLICIT True must not silently time/run the fallback — a
        # sweep would record noise winners and callers would believe
        # they exercised the compiled route
        raise ValueError(
            f"ragged_paged_attention: use_pallas=True but shapes are not "
            f"Mosaic-aligned (q {q.shape}, pages {k_pages.shape}: need "
            f"d % 64 == 0, page % 8 == 0, nh % kvh == 0)")
    if use_pallas:
        return _pallas_path(q, k_pages, v_pages, q_start, q_len, kv_len,
                            page_table, scale,
                            interpret=not _on_tpu(), block_q=block_q)
    return _dense_fallback(q, k_pages, v_pages, q_start, q_len, kv_len,
                           page_table, scale)


def _dense_fallback(q, k_pages, v_pages, q_start, q_len, kv_len,
                    page_table, scale):
    """Exact jnp reference: gather each row's sequence KV dense, one
    causal softmax per row. Memory is O(T * pages_per_seq * page).

    Float-op ORDER deliberately mirrors paged_attention._dense_fallback
    (q scaled in input dtype, -inf masking, jax.nn.softmax before the
    value contraction): a decode row here is bitwise-identical to the
    single-token decode kernel's fallback, so the chunked engine's
    greedy argmax cannot flip against the bucketed one at bf16
    near-ties."""
    T, nh, d = q.shape
    kvh, _, page, _ = k_pages.shape
    B, ppmax = page_table.shape
    S = ppmax * page
    sid, local, valid_row = _row_ids(T, q_start, q_len)
    pos = kv_len[sid] - q_len[sid] + local               # abs position
    q = q * scale                                        # pre-scale, q dtype

    def gather(pages):                                   # -> [B, S, kvh, d]
        x = pages[:, page_table]          # [kvh, B, ppmax, page, d]
        x = jnp.moveaxis(x, 0, 3)         # [B, ppmax, page, kvh, d]
        return x.reshape(B, S, kvh, d)

    k = gather(k_pages)[sid]                             # [T, S, kvh, d]
    v = gather(v_pages)[sid]
    rep = nh // kvh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("thd,tshd->ths", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    kv_pos = jnp.arange(S)
    mask = ((kv_pos[None, :] <= pos[:, None])
            & (kv_pos[None, :] < kv_len[sid][:, None])
            & valid_row[:, None])
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ths,tshd->thd", p, v.astype(jnp.float32))
    # fully-masked rows (padding / idle slots) softmax to nan: drop them
    o = jnp.where(valid_row[:, None, None], o, 0.0)
    return o.astype(q.dtype)


def _pallas_path(q, k_pages, v_pages, q_start, q_len, kv_len, page_table,
                 scale, interpret, block_q=None):
    """Repack rows per sequence (padded to a q block), run the kernel on
    grid (seq, head, q_block, kv_page), unpack back to packed rows. The
    repack/unpack gathers fuse into the surrounding jit."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, d = q.shape
    kvh, n_pages, page, _ = k_pages.shape
    B, ppmax = page_table.shape
    rep = nh // kvh
    bq = int(block_q) if block_q else _block_q(T)
    q_pad = -(-T // bq) * bq

    # per-sequence padded repack: row i of sequence s = packed row
    # q_start[s] + min(i, q_len[s]-1) (clamped duplicates are masked off
    # inside the kernel by the row < q_len predicate)
    i = jnp.arange(q_pad)
    safe = jnp.maximum(q_len, 1)
    rows = q_start[:, None] + jnp.minimum(i[None, :], safe[:, None] - 1)
    rows = jnp.clip(rows, 0, T - 1)
    qp = jnp.moveaxis(q[rows], 2, 1)                 # [B, nh, q_pad, d]

    grid = (B, nh, q_pad // bq, ppmax)

    def kern(ql_ref, kl_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
             m_s, l_s, acc):
        s = pl.program_id(0)
        qi = pl.program_id(2)
        j = pl.program_id(3)
        nk = pl.num_programs(3)

        @pl.when(j == 0)
        def _init():
            m_s[...] = jnp.full_like(m_s[...], _NEG)
            l_s[...] = jnp.zeros_like(l_s[...])
            acc[...] = jnp.zeros_like(acc[...])

        qln = ql_ref[s]
        kln = kl_ref[s]
        qb = q_ref[0, 0].astype(jnp.float32)         # [bq, d]
        kb = k_ref[0, 0].astype(jnp.float32)         # [page, d]
        vb = v_ref[0, 0].astype(jnp.float32)
        row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        pos = kln - qln + row                        # abs position [bq, 1]
        col = j * page + jax.lax.broadcasted_iota(jnp.int32, (bq, page), 1)
        valid = (row < qln) & (col <= pos) & (col < kln)
        sc = jnp.dot(qb, kb.T,
                     preferred_element_type=jnp.float32) * scale
        sc = jnp.where(valid, sc, _NEG)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        # explicit zeroing: fully-masked rows must contribute l=0, o=0
        p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(
            p, vb, preferred_element_type=jnp.float32)
        m_s[...] = m_new

        @pl.when(j == nk - 1)
        def _emit():
            l = l_s[...]
            o_ref[0, 0] = jnp.where(
                l > 0.0, acc[...] / jnp.where(l > 0.0, l, 1.0), 0.0)

    # the per-sequence page gather rides the index map: kv grid step j
    # fetches pool page page_table[s, j] (0 = the engine's scratch page
    # for table slots past the sequence's pages — masked off above)
    q_spec = pl.BlockSpec((1, 1, bq, d),
                          lambda s, h, qi, j, ql, kl, pt: (s, h, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, page, d),
        lambda s, h, qi, j, ql, kl, pt: (h // rep, pt[s, j], 0, 0))
    out_spec = pl.BlockSpec((1, 1, bq, d),
                            lambda s, h, qi, j, ql, kl, pt: (s, h, qi, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
    )
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, q_pad, d), jnp.float32),
        compiler_params=None if interpret else params,
        interpret=interpret,
        name="ragged_paged_attention",
    )(q_len.astype(jnp.int32), kv_len.astype(jnp.int32),
      page_table.astype(jnp.int32), qp, k_pages, v_pages)

    # unpack [B, nh, q_pad, d] -> packed [T, nh, d]
    sid, local, valid_row = _row_ids(T, q_start, q_len)
    local = jnp.clip(local, 0, q_pad - 1)
    o = jnp.moveaxis(out, 1, 2)[sid, local]          # [T, nh, d]
    o = jnp.where(valid_row[:, None, None], o, 0.0)
    return o.astype(q.dtype)


def sweep_block_sizes(q_shape, pages_shape, ppmax=8, iters=8, sweep=None):
    """Register/refresh the q-block winner for one packed-size class with
    kernels/autotune.py (PADDLE_AUTOTUNE=1 or sweep=True; cached winners
    are consulted by _block_q unconditionally)."""
    from . import autotune
    T, nh, d = q_shape
    kvh, n_pages, page, _ = pages_shape
    key = autotune.cache_key("ragged_paged_attn", T=_size_class(T))

    def make_fn(bq):
        if bq > _size_class(T):
            return None
        rng = jax.random.PRNGKey(0)
        q = jax.random.normal(rng, q_shape, jnp.float32)
        kp = jax.random.normal(rng, pages_shape, jnp.float32)
        vp = jax.random.normal(rng, pages_shape, jnp.float32)
        B = max(1, T // 4)
        q_len = jnp.full((B,), T // B, jnp.int32)
        q_start = jnp.arange(B, dtype=jnp.int32) * (T // B)
        kv_len = q_len + page
        pt = jnp.tile(jnp.arange(1, ppmax + 1, dtype=jnp.int32) % n_pages,
                      (B, 1))

        def run():
            def body(c, _):
                o = ragged_paged_attention(q + c, kp, vp, q_start, q_len,
                                           kv_len, pt, use_pallas=True,
                                           block_q=bq)
                return c + 0 * o[0, 0, 0], None
            return jax.jit(lambda: jax.lax.scan(
                body, jnp.float32(0), None, length=iters))()

        return run

    return autotune.autotune(key, [8, 16, 32, 64, 128], make_fn,
                             default=_block_q(T), iters=iters, sweep=sweep)
