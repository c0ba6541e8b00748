"""Fused blockwise softmax cross-entropy for large vocabularies
(ref: phi/kernels/gpu/cross_entropy_kernel.cu — the reference fuses
softmax+CE in one kernel; re-designed here flash-style for TPU).

The naive path materializes log_softmax(logits) in f32 — for a LLaMA
batch (B*S=8k, V=32k) that is a ~1 GB HBM round trip in each direction.
This kernel streams vocab blocks through VMEM with an online-softmax
accumulator (m, l) so the f32 [N, V] tensor never exists:

  forward : per token, running max m and sum-exp l over vocab blocks,
            plus the logit at the label; loss = log l + m - x[label].
  backward: dx = (exp(x - m)/l - onehot) * g, recomputed blockwise from
            the saved (m, l) residuals — same trick flash attention uses.

Grid is (token_blocks, vocab_blocks) with the vocab dimension sequential
("arbitrary") so the accumulator carries across vocab steps in VMEM
scratch. Out-of-range vocab columns (non-divisible V) are masked with
-inf; padded token rows are handled by Pallas dropping out-of-bounds
writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._tpu import on_tpu as _on_tpu

__all__ = ["fused_cross_entropy", "supported"]

_NEG_INF = -1e30


def supported(n_classes: int, min_vocab: int = 4096) -> bool:
    """Worth routing through the kernel: big-vocab CE on TPU.
    FLAGS_use_fused_ce=0 forces the plain-XLA log_softmax path (the
    per-route ablation lever; ref: phi autotune/deterministic kill
    switches)."""
    try:
        from ..framework import core
        if not core.get_bool_flag("FLAGS_use_fused_ce", False):
            return False
    except Exception:
        pass
    return _on_tpu() and n_classes >= min_vocab


def _fwd_kernel(x_ref, lbl_ref, loss_ref, m_out, l_out,
                m_s, l_s, xl_s, *, v_total, bv, ignore_index):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s[...])
        xl_s[...] = jnp.zeros_like(xl_s[...])

    x = x_ref[...].astype(jnp.float32)              # [bn, bv]
    bn = x.shape[0]
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    x = jnp.where(cols < v_total, x, _NEG_INF)

    m_prev = m_s[...]                               # [bn, 1]
    bm = jnp.max(x, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, bm)
    l_s[...] = (l_s[...] * jnp.exp(m_prev - m_new)
                + jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True))
    m_s[...] = m_new

    lbl = lbl_ref[...]                              # [bn, 1] int32
    hit = cols == lbl
    xl_s[...] += jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _finish():
        valid = lbl != ignore_index
        loss = jnp.log(l_s[...]) + m_s[...] - xl_s[...]
        loss_ref[...] = jnp.where(valid, loss, 0.0)
        m_out[...] = m_s[...]
        l_out[...] = l_s[...]


def _bwd_kernel(x_ref, lbl_ref, m_ref, l_ref, g_ref, dx_ref,
                *, v_total, bv, ignore_index):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    bn = x.shape[0]
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    lbl = lbl_ref[...]
    valid = (lbl != ignore_index).astype(jnp.float32)
    p = jnp.exp(x - m_ref[...]) / l_ref[...]
    onehot = (cols == lbl).astype(jnp.float32)
    g = g_ref[...] * valid
    dx = (p - onehot) * g
    dx = jnp.where(cols < v_total, dx, 0.0)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _block_sizes(n, v, blocks=None):
    """Token/vocab block sizes: explicit override (sweeps), else the
    autotune cache winner for this (N, V) class, else the heuristic."""
    if blocks is None:
        from . import autotune
        blocks = autotune.lookup(autotune.cache_key("fused_ce", N=n, V=v))
    if blocks is not None:
        return min(blocks[0], n), min(blocks[1], v)
    bn = 256 if n >= 256 else max(8, n)
    bv = 2048 if v >= 2048 else v
    return bn, bv


def _pallas_common(n, v, bn, bv):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = (pl.cdiv(n, bn), pl.cdiv(v, bv))
    x_spec = pl.BlockSpec((bn, bv), lambda i, j: (i, j))
    row_spec = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    return pl, pltpu, grid, x_spec, row_spec, params


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_cross_entropy(logits, labels, ignore_index=-100, blocks=None):
    """Per-token CE loss [N] f32 from logits [N, V] + labels [N] int.
    ignore_index rows get loss 0 (caller divides by the valid count).
    blocks: optional (bn, bv) override used by autotune sweeps."""
    loss, _ = _fwd(logits, labels, ignore_index, blocks)
    return loss


def _fwd(logits, labels, ignore_index, blocks=None):
    n, v = logits.shape
    bn, bv = _block_sizes(n, v, blocks)
    pl, pltpu, grid, x_spec, row_spec, params = _pallas_common(n, v, bn, bv)
    lbl2 = labels.astype(jnp.int32).reshape(n, 1)
    kern = functools.partial(_fwd_kernel, v_total=v, bv=bv,
                             ignore_index=ignore_index)
    # inside a shard_map that tracks variance (distributed/sharding.
    # shard_kernel) the outputs vary as the rows do; empty outside
    out_shape = [jax.ShapeDtypeStruct((n, 1), jnp.float32,
                                      vma=jax.typeof(logits).vma)] * 3
    interpret = not _on_tpu()
    loss, m, l = pl.pallas_call(
        kern, grid=grid,
        in_specs=[x_spec, row_spec],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 3,
        compiler_params=None if interpret else params,
        interpret=interpret,
        name="fused_ce_fwd",
    )(logits, lbl2)
    return loss[:, 0], (logits, lbl2, m, l)


def _fwd_rule(logits, labels, ignore_index, blocks=None):
    return _fwd(logits, labels, ignore_index, blocks)


def _bwd_rule(ignore_index, blocks, res, g):
    logits, lbl2, m, l = res
    n, v = logits.shape
    bn, bv = _block_sizes(n, v, blocks)
    pl, pltpu, grid, x_spec, row_spec, params = _pallas_common(n, v, bn, bv)
    kern = functools.partial(_bwd_kernel, v_total=v, bv=bv,
                             ignore_index=ignore_index)
    interpret = not _on_tpu()
    dx = pl.pallas_call(
        kern, grid=grid,
        in_specs=[x_spec, row_spec, row_spec, row_spec, row_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct((n, v), logits.dtype,
                                       vma=jax.typeof(logits).vma),
        compiler_params=None if interpret else params,
        interpret=interpret,
        name="fused_ce_bwd",
    )(logits, lbl2, m, l, g.astype(jnp.float32).reshape(n, 1))
    return dx, None


fused_cross_entropy.defvjp(_fwd_rule, _bwd_rule)


def sweep_block_sizes(N=8192, V=32000, dtype=jnp.bfloat16,
                      candidates=None, iters=8, resweep=False):
    """On-chip (bn, bv) sweep for the fused-CE kernel; winners persist in
    the autotune cache (ref: phi/kernels/autotune/cache.cc). Tunes the
    training shape: fwd + bwd under grad."""
    from . import autotune

    if candidates is None:
        candidates = [(bn, bv)
                      for bn in (128, 256, 512) if bn <= N
                      for bv in (1024, 2048, 4096, 8192) if bv <= V]
    key = autotune.cache_key("fused_ce", N=N, V=V)
    kq = jax.random.split(jax.random.PRNGKey(0), 2)
    logits = jax.random.normal(kq[0], (N, V), dtype)
    labels = jax.random.randint(kq[1], (N,), 0, V)

    def make_fn(cand):
        def body(c, _):
            f = lambda x: fused_cross_entropy(x, labels, -100,
                                              tuple(cand)).sum()
            return c + jax.grad(f)(logits).astype(jnp.float32).sum(), None

        return jax.jit(lambda: jax.lax.scan(
            body, jnp.float32(0), None, length=iters)[0])

    return autotune.autotune(
        key, candidates, make_fn, default=list(_block_sizes(N, V)),
        iters=iters,
        sweep=True if (resweep or autotune.lookup(key) is None) else None)
