"""Paged-KV decode attention (ref: the reference's paged decode kernels —
block_multihead_attention under phi/kernels/fusion/gpu/ and
masked_multihead_attention / fused_multi_transformer_op.cu decode mode).

TPU-native: wraps the in-tree Pallas paged-attention kernel
(jax.experimental.pallas.ops.tpu.paged_attention) for single-token decode
over a paged KV cache, with a dense jnp fallback (CPU / unaligned shapes).
The page table layout matches the reference's block tables: per-sequence
page indices into a global page pool.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ._tpu import on_tpu as _on_tpu

__all__ = ["decode_attention", "paged_decode_attention", "paginate_cache",
           "supported"]

_PAGE = 16  # tokens per page (multiple of the sublane tile)


def supported(q_shape, pages_shape) -> bool:
    """q: [B, nh, d]; pages: [kvh, n_pages, page, d]."""
    if not _on_tpu():
        return False
    B, nh, d = q_shape
    kvh, n_pages, page, d2 = pages_shape
    return (d == d2 and d % 64 == 0 and page % 8 == 0
            and nh % kvh == 0)


def paginate_cache(cache_k, cache_v, page_size=_PAGE):
    """[B, S_max, kvh, d] contiguous cache -> (k_pages, v_pages,
    page_indices) in the kernel's [kvh, total_pages, page, d] pool layout
    with the identity block table."""
    B, S, kvh, d = cache_k.shape
    assert S % page_size == 0, f"S_max {S} must be a page multiple"
    ppseq = S // page_size

    def to_pages(c):
        # [B, S, kvh, d] -> [kvh, B*ppseq, page, d]
        x = c.reshape(B, ppseq, page_size, kvh, d)
        x = jnp.moveaxis(x, 3, 0)                 # [kvh, B, ppseq, page, d]
        return x.reshape(kvh, B * ppseq, page_size, d)

    page_indices = (jnp.arange(B)[:, None] * ppseq
                    + jnp.arange(ppseq)[None, :]).astype(jnp.int32)
    return to_pages(cache_k), to_pages(cache_v), page_indices


def paged_decode_attention(q, k_pages, v_pages, lengths, page_indices,
                           scale=None):
    """One decode step over a paged cache.

    q: [B, nh, d]; k/v_pages: [kvh, total_pages, page, d];
    lengths: i32[B] valid tokens per sequence;
    page_indices: i32[B, pages_per_seq].
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    q = q * scale  # kernel applies no softmax scale
    if supported(q.shape, k_pages.shape):
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention)
        # kernel requires pages_per_seq % pages_per_compute_block == 0
        ppseq = page_indices.shape[1]
        pages_per_block = next(b for b in (8, 4, 2, 1) if ppseq % b == 0)
        return paged_attention(
            q, k_pages, v_pages, lengths, page_indices,
            pages_per_compute_block=pages_per_block)
    return _dense_fallback(q, k_pages, v_pages, lengths, page_indices)


def _dense_fallback(q, k_pages, v_pages, lengths, page_indices):
    B, nh, d = q.shape
    kvh, _, page, _ = k_pages.shape
    ppseq = page_indices.shape[1]
    S = ppseq * page

    def gather(pages):  # -> [B, S, kvh, d]
        # pages[h, page_indices[b, p]] : [B, ppseq, kvh?, ...]
        x = pages[:, page_indices]                # [kvh, B, ppseq, page, d]
        x = jnp.moveaxis(x, 0, 3)                 # [B, ppseq, page, kvh, d]
        return x.reshape(B, S, kvh, d)

    k = gather(k_pages)
    v = gather(v_pages)
    rep = nh // kvh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention(q, cache_k, cache_v, cur_len, scale=None):
    """Convenience: q [B, 1, nh, d] + contiguous cache [B, S_max, kvh, d]
    -> [B, 1, nh, d]; routes through the paged kernel when eligible."""
    B = q.shape[0]
    q1 = q[:, 0]
    S = cache_k.shape[1]
    pad = (-S) % _PAGE
    if pad:
        cfg = [(0, 0), (0, pad), (0, 0), (0, 0)]
        cache_k = jnp.pad(cache_k, cfg)
        cache_v = jnp.pad(cache_v, cfg)
    kp, vp, pidx = paginate_cache(cache_k, cache_v)
    lengths = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    out = paged_decode_attention(q1, kp, vp, lengths, pidx, scale=scale)
    return out[:, None]
