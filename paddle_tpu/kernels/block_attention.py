"""Pallas block-attention kernel with softmax stats — the per-round
compute of ring attention (kernels/ring_attention.py) and the per-chunk
compute of the chunked-bias flash path (kernels/flash_attention.py).

The ring schedule needs UNNORMALIZED per-block results (m, l, o) so
rounds can merge online; the in-tree flash kernel only returns the
normalized output, which is why ring previously fell back to dense jnp
einsums (VERDICT r1 weak #7). This kernel streams k/v sub-blocks through
VMEM with an online-softmax accumulator — the s = q k^T f32 score matrix
never materializes in HBM — and carries an analytic custom VJP (einsum
recompute from the saved stats, the same fwd-kernel + analytic-VJP
pattern as kernels/rms_norm.py), so ring attention stays reverse-
differentiable through lax.scan.

Layout: q [B, Sq, H, D], k/v [B, Sk, H, D] -> m, l [B, H, Sq] f32 and
o [B, Sq, H, D] f32 (unnormalized); `mask` is an optional [Sq, Sk] bool.
`bias` is an optional ADDITIVE [B, H, Sq, Sk] f32 operand (the chunked
slice of an attention bias — alibi, relative-position, padding): entries
<= _NEG/2 are treated as masked (their p is zeroed exactly, so a fully
masked row yields l=0, o=0 like the boolean mask path). bias is
differentiable — the VJP returns ds for it.
Fully-masked rows yield (m=-1e30, l=0, o=0), which the ring merge treats
as an empty contribution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._tpu import on_tpu as _on_tpu

__all__ = ["block_attention_stats", "supported"]

_NEG = -1e30
# tests flip this to exercise the Pallas path through the interpreter on
# CPU; production dispatch requires a real TPU (interpret mode is orders
# of magnitude slower than the jnp fallback)
_FORCE_PALLAS = False


def _block_size(s: int, which: str = "q") -> int:
    """Largest dividing block <= 512, overridable by an autotune-cache
    winner for this sequence-length class (kernels/autotune.py)."""
    from . import autotune
    hit = autotune.lookup(autotune.cache_key("block_attn", S=s))
    if hit:
        b = hit[0] if which == "q" else hit[-1]
        if s % b == 0:
            return b
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    raise AssertionError(f"supported() admitted unaligned size {s}")


def supported(q_shape, k_shape) -> bool:
    B, Sq, H, D = q_shape
    Sk = k_shape[1]
    return (Sq % 128 == 0 and Sk % 128 == 0 and D % 64 == 0
            and q_shape[2] == k_shape[2])


def _pallas_fwd(q, k, v, mask, scale, bias=None, interpret=None):
    """q [N, Sq, D]; k/v [N, Sk, D]; mask [Sq, Sk] bool or None;
    bias [N, Sq, Sk] f32 or None, with N = B*H folded into the grid's
    leading parallel dim."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, Sq, D = q.shape
    Sk = k.shape[1]
    bq = _block_size(Sq, "q")   # exact divisors — no dropped tail blocks
    bk = _block_size(Sk, "k")
    grid = (N, Sq // bq, Sk // bk)
    use_mask = mask is not None
    if not use_mask:
        mask = jnp.ones((bq, bk), jnp.bool_)
    use_bias = bias is not None
    if not use_bias:
        bias = jnp.zeros((1, bq, bk), jnp.float32)

    def kern(q_ref, k_ref, v_ref, mask_ref, bias_ref, m_out, l_out, o_out,
             m_s, l_s, o_s):
        j = pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(j == 0)
        def _init():
            m_s[...] = jnp.full_like(m_s[...], _NEG)
            l_s[...] = jnp.zeros_like(l_s[...])
            o_s[...] = jnp.zeros_like(o_s[...])

        qb = q_ref[0].astype(jnp.float32)          # [bq, D]
        kb = k_ref[0].astype(jnp.float32)          # [bk, D]
        vb = v_ref[0].astype(jnp.float32)
        mb = mask_ref[...]
        s = (qb @ kb.T) * scale
        if use_bias:
            s = s + bias_ref[0]
            # bias-masked entries (<= _NEG/2) count as invalid
            mb = mb & (bias_ref[0] > 0.5 * _NEG)
        s = jnp.where(mb, s, _NEG)

        m_prev = m_s[...]                          # [bq, 1]
        bm = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, bm)
        # explicit zeroing: fully-masked rows must contribute l=0, o=0
        # (exp(-1e30 - (-1e30)) would otherwise be 1)
        p = jnp.where(mb, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        o_s[...] = o_s[...] * alpha + p @ vb
        m_s[...] = m_new

        @pl.when(j == nk - 1)
        def _emit():
            m_out[0] = m_s[...]
            l_out[0] = l_s[...]
            o_out[0] = o_s[...]

    if interpret is None:
        interpret = not _on_tpu()
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    mask_spec = (pl.BlockSpec((bq, bk), lambda n, i, j: (i, j)) if use_mask
                 else pl.BlockSpec((bq, bk), lambda n, i, j: (0, 0)))
    bias_spec = (pl.BlockSpec((1, bq, bk), lambda n, i, j: (n, i, j))
                 if use_bias
                 else pl.BlockSpec((1, bq, bk), lambda n, i, j: (0, 0, 0)))
    m, l, o = pl.pallas_call(
        kern, grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, bk, D), lambda n, i, j: (n, j, 0)),
            pl.BlockSpec((1, bk, D), lambda n, i, j: (n, j, 0)),
            mask_spec,
            bias_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, 1), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, bq, D), lambda n, i, j: (n, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, Sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, Sq, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=None if interpret else params,
        interpret=interpret,
        name="block_attention_stats",
    )(q, k, v, mask, bias)
    return m[..., 0], l[..., 0], o


def _apply_bias_mask(s, mask, bias):
    """Shared score assembly: additive bias, then boolean/threshold mask.
    Returns (s, valid) with valid broadcast to s's shape."""
    valid = jnp.ones(s.shape, bool) if mask is None else \
        jnp.broadcast_to(mask[None, None], s.shape)
    if bias is not None:
        s = s + bias
        valid = valid & (bias > 0.5 * _NEG)
    return jnp.where(valid, s, _NEG), valid


def _dense_stats(q, k, v, mask, scale, bias=None):
    """jnp reference path: same contract, used for unaligned shapes."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s, valid = _apply_bias_mask(s, mask, bias)
    m = jnp.max(s, axis=-1)
    p = jnp.where(valid, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return m, l, o


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 6))
def block_attention_stats(q, k, v, mask, scale, bias=None, use_pallas=None):
    """(m [B,H,Sq], l [B,H,Sq], o [B,Sq,H,D] f32, unnormalized) for one
    ring round / bias chunk. Differentiable in q/k/v/bias; mask is
    non-differentiable. use_pallas: None = auto (real TPU + aligned),
    True/False forces the route (the chunked-bias caller decides once
    per call site so cross-platform lowering tests can pin it)."""
    return _stats_fwd_impl(q, k, v, mask, scale, bias, use_pallas)


def _stats_fwd_impl(q, k, v, mask, scale, bias=None, use_pallas=None):
    B, Sq, H, D = q.shape
    explicit = use_pallas is True
    if use_pallas is None:
        use_pallas = supported(q.shape, k.shape) and (_on_tpu()
                                                      or _FORCE_PALLAS)
    if use_pallas and supported(q.shape, k.shape):
        # an EXPLICIT True (lowering tests / the TPU bias route) compiles
        # the real Mosaic kernel even when tracing off-chip; the
        # _FORCE_PALLAS auto route keeps the interpreter for CPU CI
        interpret = None if not explicit else False
        fold = lambda x: jnp.swapaxes(x, 1, 2).reshape(
            B * H, x.shape[1], D)
        bias_f = None
        if bias is not None:
            bias_f = jnp.broadcast_to(
                bias.astype(jnp.float32),
                (B, H, Sq, k.shape[1])).reshape(B * H, Sq, k.shape[1])
        m, l, o = _pallas_fwd(fold(q), fold(k), fold(v), mask, scale,
                              bias_f, interpret=interpret)
        m = m.reshape(B, H, Sq)
        l = l.reshape(B, H, Sq)
        o = jnp.swapaxes(o.reshape(B, H, Sq, D), 1, 2)
        return m, l, o
    return _dense_stats(q, k, v, mask, scale, bias)


def _stats_fwd(q, k, v, mask, scale, bias, use_pallas):
    out = _stats_fwd_impl(q, k, v, mask, scale, bias, use_pallas)
    m = out[0]
    return out, (q, k, v, mask, bias, m)


def _stats_bwd(scale, use_pallas, res, cts):
    """Analytic VJP with m treated as stop-gradient (the merged, final
    attention output is invariant to the stabilizer):
      dp[q,k] = do[q]·v[k] + dl[q];  ds = p * dp
      dq = ds k * scale; dk = ds^T q * scale; dv = p^T do; dbias = ds.
    p is recomputed from the saved m — one [Sq, Sk] block per ring round
    / bias chunk, never the full sequence."""
    q, k, v, mask, bias, m = res
    ct_m, ct_l, ct_o = cts
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    s, valid = _apply_bias_mask(s, mask, bias)
    p = jnp.where(valid, jnp.exp(s - m[..., None]), 0.0)
    do = ct_o.astype(jnp.float32)                       # [B,Sq,H,D]
    dp = jnp.einsum("bqhd,bkhd->bhqk", do, vf) + ct_l[..., None]
    ds = p * dp
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, do)
    dbias = None
    if bias is not None:
        # reduce ds over the broadcast dims of the given bias shape
        dbias = ds
        for ax in range(4):
            if bias.shape[ax] == 1 and ds.shape[ax] != 1:
                dbias = dbias.sum(axis=ax, keepdims=True)
        dbias = dbias.astype(bias.dtype)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, dbias)


block_attention_stats.defvjp(_stats_fwd, _stats_bwd)
