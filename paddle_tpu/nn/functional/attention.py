"""Attention functionals (ref: python/paddle/nn/functional/flash_attention.py:147
flash_attn; phi/kernels/gpu/flash_attn_kernel.cu).

TPU-native: routes to the in-repo Pallas flash-attention kernel when shapes
allow (paddle_tpu/kernels/flash_attention.py), else a fused XLA softmax path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...autograd.tape import apply_op
from ...framework import core
from ...ops._helpers import to_tensor_like, unwrap

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdp_kernel", "sparse_attention",
           "gated_delta_rule"]


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale):
    """[B, S, H, D] paddle layout; computed in f32 for stability."""
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = (qt @ jnp.swapaxes(kt, -1, -2)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(cm, s, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            s = jnp.where(mask, s, -jnp.inf)
        else:
            s = s + mask.astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    out = p @ vt
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _as_padding_mask(mask, batch, kv_len):
    """Convert a keep/drop mask that provably varies only along the kv axis
    to a [B, kv_len] validity mask; None if not convertible.

    Convertible shapes: [kv], [B, kv], [B, 1, kv], [B, 1, 1, kv] — the
    broadcast dims prove kv-only variation. Only BOOLEAN masks convert:
    they are pure keep/drop, so segment-id masking is exact. Additive float
    masks may carry finite biases that segment ids cannot represent, so
    they always take the dense path.
    """
    if mask.dtype != jnp.bool_:
        return None
    shape = tuple(mask.shape)
    ok = (shape == (kv_len,) or shape == (batch, kv_len)
          or shape == (batch, 1, kv_len) or shape == (batch, 1, 1, kv_len))
    if not ok:
        return None
    flat = mask.reshape(shape[0] if len(shape) > 1 else 1, kv_len)
    if len(shape) == 1:
        flat = jnp.broadcast_to(flat, (batch, kv_len))
    return flat


def _bias_broadcastable(mask_shape, q_shape, k_shape) -> bool:
    """mask broadcastable to [B, H, Sq, Sk] (numpy rules, trailing dims)."""
    target = (q_shape[0], q_shape[2], q_shape[1], k_shape[1])
    if len(mask_shape) > 4:
        return False
    for got, want in zip(reversed(mask_shape), reversed(target)):
        if got != 1 and got != want:
            return False
    return True


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """Layout [batch, seq, heads, head_dim] (paddle flash_attn convention)."""
    q, k, v = to_tensor_like(query), to_tensor_like(key), to_tensor_like(value)
    scale = 1.0 / math.sqrt(q.shape[-1])

    use_pallas = False
    pad_convertible = False
    bias_route = False
    try:
        from ...kernels import flash_attention as fa
        raw_mask = unwrap(attn_mask) if attn_mask is not None else None
        if raw_mask is not None:
            pad_convertible = _as_padding_mask(
                raw_mask, q.shape[0], k.shape[1]) is not None
            # anything broadcastable to [B, H, Sq, Sk] that is NOT a pure
            # kv padding mask rides the kernel's additive-bias operand —
            # never a silent dense fallback (ref flash_attn_kernel.cu
            # accepts an attn_mask tensor the same way)
            bias_route = (not pad_convertible and raw_mask.ndim <= 4
                          and _bias_broadcastable(
                              raw_mask.shape, q.shape, k.shape))
        use_pallas = fa.supported(
            q.shape, k.shape, attn_mask is None or pad_convertible,
            has_bias=bias_route)
    except Exception:
        use_pallas = False

    if use_pallas and dropout_p == 0.0:
        from ...kernels import flash_attention as fa
        B, Sk = q.shape[0], k.shape[1]
        if attn_mask is not None and pad_convertible:
            def _flash_masked(a, b, c, m):
                return fa.flash_attention_bshd(
                    a, b, c, causal=is_causal, scale=scale,
                    padding_mask=_as_padding_mask(m, B, Sk))

            return apply_op(_flash_masked, q, k, v, to_tensor_like(attn_mask),
                            name="flash_attention")
        if attn_mask is not None:  # bias route
            def _flash_bias(a, b, c, m):
                bias = (jnp.where(m, 0.0, -1e30).astype(jnp.float32)
                        if m.dtype == jnp.bool_ else m)
                return fa.flash_attention_bshd(
                    a, b, c, causal=is_causal, scale=scale, bias=bias)

            return apply_op(_flash_bias, q, k, v, to_tensor_like(attn_mask),
                            name="flash_attention")
        return apply_op(lambda a, b, c: fa.flash_attention_bshd(
            a, b, c, causal=is_causal, scale=scale), q, k, v,
            name="flash_attention")

    mask = unwrap(attn_mask) if attn_mask is not None else None
    out = apply_op(lambda a, b, c: _sdpa_ref(a, b, c, mask, dropout_p,
                                             is_causal, scale),
                   q, k, v, name="sdpa")
    if dropout_p > 0.0 and training:
        from .common import dropout as _dropout
        out = _dropout(out, p=dropout_p, training=True)
    return out


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def _packed_segments(cu, total):
    """cu_seqlens [n+1] -> per-token segment ids [total], 1-BASED so the
    kernel's alignment padding (segment 0) can never attend to or from a
    real sequence (segment equality is the kernel's mask)."""
    return jnp.cumsum(jnp.zeros(total, jnp.int32)
                      .at[cu[1:-1]].add(1)) + 1


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen flash attention over PACKED sequences
    (ref: flash_attn_unpadded / flash_attn_varlen kernel).

    TPU route: the Pallas flash kernel with batch 1 + per-token SEGMENT
    IDS built from cu_seqlens — cross-sequence attention is segment-
    masked, and global causal + packing order equals per-sequence causal
    when q/kv share the packing (self-attention). Packed GQA rides the
    splash kernel's MQA mode with the same segment ids (no kv repeat).
    Dense fallback otherwise (CPU, mismatched q/kv packings under
    causal).
    """
    q = to_tensor_like(query)   # [total_q, H, D]
    k = to_tensor_like(key)
    v = to_tensor_like(value)
    cq = unwrap(cu_seqlens_q)
    ck = unwrap(cu_seqlens_k)

    from ...kernels import flash_attention as fa
    causal_ok = True
    if causal:
        # causal packing only valid when q/kv pack identically; under
        # jit the offsets may be tracers (host-uncomparable) — object
        # identity (the standard self-attention call) still decides
        if cq is ck or cu_seqlens_q is cu_seqlens_k:
            causal_ok = True
        else:
            try:
                import numpy as _np
                causal_ok = _np.array_equal(_np.asarray(cq),
                                            _np.asarray(ck))
            except Exception:
                causal_ok = False
    # dropout is inert outside training — don't let an inference call
    # with a configured dropout fall to the O(total^2) dense path
    if ((dropout == 0.0 or not training) and causal_ok
            and fa.packed_supported(q.shape[0], k.shape[0],
                                    q.shape[1], k.shape[1], q.shape[2])):
        def fk(qq, kk, vv):
            return fa.flash_attention_packed(
                qq, kk, vv, _packed_segments(cq, qq.shape[0]),
                _packed_segments(ck, kk.shape[0]), causal=causal,
                scale=scale)

        return apply_op(fk, q, k, v, name="flash_attn_unpadded"), None

    def f(qq, kk, vv):
        total_q = qq.shape[0]
        total_k = kk.shape[0]
        if qq.shape[1] != kk.shape[1]:       # GQA dense fallback
            rep = qq.shape[1] // kk.shape[1]
            kk = jnp.repeat(kk, rep, axis=1)
            vv = jnp.repeat(vv, rep, axis=1)
        seg_q = jnp.cumsum(
            jnp.zeros(total_q, jnp.int32).at[cq[1:-1]].add(1))
        seg_k = jnp.cumsum(
            jnp.zeros(total_k, jnp.int32).at[ck[1:-1]].add(1))
        s = jnp.einsum("qhd,khd->hqk", qq.astype(jnp.float32),
                       kk.astype(jnp.float32)) * scale
        valid = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(total_q) - cq[seg_q]
            pos_k = jnp.arange(total_k) - ck[seg_k]
            valid = valid & (pos_k[None, :] <= pos_q[:, None])
        s = jnp.where(valid[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)
        out = jnp.einsum("hqk,khd->qhd", p, vv.astype(jnp.float32))
        return out.astype(qq.dtype)

    out = apply_op(f, q, k, v, name="flash_attn_unpadded")
    return out, None


class sdp_kernel:
    """Context selecting attention backends (torch-compat shim)."""

    def __init__(self, enable_flash=True, enable_math=True,
                 enable_mem_efficient=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """ref: nn/functional/sparse_attention.py:19 — attention restricted
    to a CSR-expressed sparsity pattern. q/k/v: [B, H, S, D];
    offset [B, H, S+1], columns [B, H, nnz] describe the per-row
    attended columns. The masked-softmax body is shared with
    paddle_tpu.sparse.attention (_masked_attention_core); this wrapper
    adds the CSR->bool-pattern decode and the differentiable tape op."""
    import numpy as _np

    q = to_tensor_like(query)
    k = to_tensor_like(key)
    v = to_tensor_like(value)
    B, H, S, D = q.shape
    # the sparsity pattern is static STRUCTURE (host metadata, like the
    # reference's CSR descriptors): materialize the [B, H, S, S] bool
    # mask once on the host
    off = _np.asarray(unwrap(to_tensor_like(sparse_csr_offset))
                      ).reshape(B, H, S + 1)
    cols = _np.asarray(unwrap(to_tensor_like(sparse_csr_columns))
                       ).reshape(B, H, -1)
    pat = _np.zeros((B, H, S, S), bool)
    counts = _np.diff(off, axis=-1)                  # [B, H, S]
    rows = _np.repeat(_np.tile(_np.arange(S), B * H).reshape(B, H, S),
                      counts.reshape(-1),
                      axis=None)                     # flat row per nnz
    bh = _np.repeat(_np.arange(B * H), counts.reshape(B * H, -1).sum(-1))
    pat.reshape(B * H, S, S)[bh, rows, cols.reshape(-1)] = True

    extra = []
    kp_present = key_padding_mask is not None
    am_present = attn_mask is not None
    if kp_present:
        extra.append(to_tensor_like(key_padding_mask))
    if am_present:
        extra.append(to_tensor_like(attn_mask))

    def f(qd, kd, vd, *rest):
        it = iter(rest)
        mask = jnp.asarray(pat)
        if kp_present:
            kpm = next(it)
            mask = mask & (kpm[:, None, None, :] != 0)
        if am_present:
            am = next(it)
            mask = mask & (am[None, None] != 0 if am.ndim == 2
                           else am != 0)
        from ...sparse import _masked_attention_core
        return _masked_attention_core(qd, kd, vd, mask)

    return apply_op(f, q, k, v, *extra, name="sparse_attention")


def gated_delta_rule(query, key, value, log_decay, beta, chunk=64,
                     scale=None):
    """Gated delta-rule linear attention with a per-channel decay:
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t * scale, from a zero state, computed a chunk of
    tokens at a time (kernels/gated_delta_rule.py). query, key, log_decay
    [B, T, H, dk], value [B, T, H, dv], beta [B, T, H]; differentiable in
    all five. Returns [B, T, H, dv]."""
    from ...kernels.gated_delta_rule import chunk_gated_delta_rule

    def fn(q, k, v, g, b):
        return chunk_gated_delta_rule(q, k, v, g, b, chunk=chunk,
                                      scale=scale)

    return apply_op(fn, *(to_tensor_like(t) for t in (
        query, key, value, log_decay, beta)), name="gated_delta_rule")
