"""Flash attention on TPU (ref: phi/kernels/gpu/flash_attn_kernel.cu +
third_party flashattn — re-designed for TPU, not ported; the reference
kernel's MQA/GQA + bias support is matched here, flash_attn_kernel.cu
accepts num_heads_k != num_heads and an attn additive mask).

Three routes, all Pallas:
- MHA (q_heads == kv_heads), no window, values as wide as keys: the
  tuned in-tree TPU flash kernel
  (jax.experimental.pallas.ops.tpu.flash_attention) — online-softmax
  MXU-shaped tiles, native causal block skipping, segment-id padding
  masks, and an additive-bias operand (`ab`) for arbitrary masks.
- GQA/MQA causal/full without bias: the splash kernel in MQA mode,
  vmapped over kv heads with q grouped [kv_heads, group, Sq, D] — no
  materialized kv repeat, and block-sparse causal skipping. The same
  route takes a sliding `window` (a query sees its own position and the
  window - 1 before it: splash's `LocalMask`, blocks outside the band
  skipped) and values of another width than the keys (latent attention:
  keys no-rope | rope, values narrower); with either, MHA goes this way
  too, as groups of one.
- GQA with bias: kv heads broadcast to q heads (autodiff sums the kv
  grads over the group), then the MHA route — still the flash kernel,
  never the O(S^2) dense fallback.

Block sizes come from the autotune cache (kernels/autotune.py) when a
sweep has recorded a winner for the shape class, else a 512 heuristic.

The splash route's backward has two forms and `splash_backward` chooses
between them from the call's shape and mask alone: under a dense mask
(causal or full) ONE kernel makes dq, dk and dv from one set of a tile's
scores, dq leaving it once an outer kv block (the widest of 4096 / 2048 /
1024 / 512 that divides Sk and fits VMEM at the call's key and value
widths, and under a causal mask at most a quarter of Sk) as copies that
are summed after, a few kv heads a kernel call where all heads' copies at
once would hold more than a stated number of bytes; under a window the
dkv and dq kernels run one after the other as they did. The set-up event
`train_step.splash_backward` says which a traced call took.

What the backward needs from the forward has a name. The splash route
stamps its forward's `out` and `logsumexp` (the residuals of the kernel's
`custom_vjp`) with `SPLASH_RESIDUALS`, and `jit.TrainStep`'s default remat
policy keeps that name (`jit.KEPT_CHECKPOINT_NAMES`): under a
`jax.checkpoint` armed with it the forward kernel runs once a step and
the backward kernels read the stored arrays; under `remat_policy=None` or
"nothing" it runs again in the backward, as everything else does. With
no surrounding checkpoint, or no differentiation (serving prefill), the
stamp is an identity. MHA through splash (a window, or values of their
own width) is stamped like GQA; plain MHA stays on jax's older flash
kernel, which takes no name: its forward is recomputed under every
policy, and its callers lower as they did.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ._tpu import on_tpu as _on_tpu

# checkpoint_name of the splash forward's (out, logsumexp): see the
# module docstring
SPLASH_RESIDUALS = "splash_residuals"
# lane width is 128; the kernel pads smaller head dims, profitable down to 64
_MIN_HEAD_DIM = 64
_SEQ_ALIGN = 128


def supported(q_shape, k_shape, causal_or_none: bool,
              has_padding_mask: bool = False,
              has_bias: bool = False, v_dim=None, window=None) -> bool:
    """True when flash_attention_bshd will hit a Pallas kernel.

    `causal_or_none`: mask is either causal or absent. Arbitrary
    additive masks route through `bias=` (the kernel's ab operand), so
    pass has_bias=True for those instead of returning False. Padding
    masks map to segment ids. GQA/MQA (q_heads a multiple of kv_heads)
    is first-class. `v_dim`: the values' head width where it is not the
    keys'; `window`: positions a query sees, its own among them (both
    the splash route's, causal and without bias).
    """
    del has_padding_mask  # handled via segment ids — never gated out
    if not _on_tpu():
        return False
    if not causal_or_none and not has_bias:
        return False  # non-causal non-bias masks must come in as bias
    B, Sq, Hq, D = q_shape
    Hk = k_shape[2]
    Sk = k_shape[1]
    # kernel pads D <= 128 up to the lane width; above that it requires an
    # exact multiple of 128 (so 192/320 must take the dense fallback)
    d_ok = (D % 64 == 0) if D <= 128 else (D % 128 == 0)
    if v_dim is not None and v_dim != D:
        d_ok = d_ok and not has_bias and (
            (v_dim % 64 == 0) if v_dim <= 128 else (v_dim % 128 == 0))
    if window is not None and (has_bias or not causal_or_none):
        return False
    return (d_ok and Sq % _SEQ_ALIGN == 0 and Sk % _SEQ_ALIGN == 0
            and Hq % Hk == 0)


def _block_sizes(Sq, Sk, D, causal, blocks=None):
    """Flash BlockSizes: explicit override (sweeps), else the autotune
    cache winner for this shape class, else 512-square."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    from . import autotune
    if blocks is None:
        default = (min(512, Sq), min(512, Sk))
        key = autotune.cache_key("flash", Sq=Sq, Sk=Sk, D=D,
                                 causal=int(causal))
        blocks = autotune.lookup(key) or default
    bq, bk = min(blocks[0], Sq), min(blocks[1], Sk)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
    )


def _splash_blocks(Sq, Sk, D, blocks=None):
    """(block_q, block_kv) of the splash kernels: explicit override
    (sweeps), else the autotune cache's winner, else 512-square."""
    from . import autotune
    if blocks is None:
        default = (min(512, Sq), min(512, Sk))
        key = autotune.cache_key("splash", Sq=Sq, Sk=Sk, D=D)
        blocks = autotune.lookup(key) or default
    return min(blocks[0], Sq), min(blocks[1], Sk)


class SplashBackward(NamedTuple):
    """How one splash call's backward is made (`splash_backward`)."""
    form: str            # "one_kernel" | "two_kernels"
    block_kv_dkv: int    # the dkv kernel's OUTER kv block
    partials: int        # copies of dq that leave the one kernel (0: none)
    partial_bytes: int   # what the copies live at one time hold in HBM
    kv_heads_a_call: int  # kv heads one kernel call takes (the rest after)


# The one-kernel backward's outer kv block: the widest of these that
# divides Sk and fits Mosaic's scoped VMEM (the library's call sets no
# limit of its own, so the chip's default holds: 16 MiB on a v5e).
_OUTER_KV_BLOCKS = (4096, 2048, 1024, 512)
_SCOPED_VMEM_BYTES = 16 * 2 ** 20
# Under a causal mask the outer block is at most a quarter of Sk: the one
# kernel computes every compute block of an outer block that holds a live
# tile, so an outer block on the diagonal runs its masked tiles too, a
# share (w - 1) / (n + 1) of the triangle's (w compute blocks an outer
# block, n a row). A quarter keeps that under an eighth; at a half it is a
# third, and a Xing call (S = 4096) read 1.18 ms at 2048 where 1.06 at
# 1024 (my chip run, PR 50).
_CAUSAL_OUTER_SHARE = 4
# dq leaves the one kernel as Sk // block_kv_dkv copies of q, summed
# after: temporaries of the backward. A call whose copies would hold more
# than this goes a few kv heads at a time, one kernel call after the
# other, so that only one call's copies are live; where one kv head's
# copies alone hold more, the call keeps two kernels. The cells' calls
# hold 0.13-0.67 GB whole (GLM 16 copies of 5 heads x 16384 x 256, Solar
# 8 of 8 x 32768 x 128) against 2.3-5.4 GiB of headroom; Granite's one
# call of all 32 heads at 32768 would hold 4.3 GB (16 copies, 64-wide
# heads stored 128 lanes wide) where the step has 2.2 GiB, and goes 2 of
# its 8 kv heads at a time: PERF.md section 6, PR 50.
_ONE_KERNEL_MAX_PARTIAL_BYTES = 2 ** 30


def _lanes(d):
    return -(-d // 128) * 128


def _one_kernel_vmem_bytes(bq, bkv, D, Dv, itemsize, batched):
    """What the one-kernel backward's blocks take of VMEM, as the chip's
    compiler counted them (sandbox compiles, PR 50): k, v in and dk, dv
    out twice buffered, dk and dv float32 scratches (twice buffered too
    once the call is vmapped over more than one kv head), and the q-side
    blocks: q, do, dq out twice buffered, dq's float32 scratch."""
    wide = _lanes(D) + _lanes(Dv)
    kv_side = bkv * wide * (4 * itemsize + (8 if batched else 4))
    q_side = bq * (2 * itemsize * wide + _lanes(D) * (2 * itemsize + 4))
    return kv_side + q_side


def splash_backward(q_shape, kv_heads, Sk, v_dim, dtype, causal, window,
                    blocks=None):
    """The form of one splash call's backward, from the call's own shape
    and mask and nothing else. q_shape [B, Hq, Sq, D].

    One kernel (the library's `use_fused_bwd_kernel`) makes a tile's
    scores and probabilities once for dq, dk and dv: five products, one
    `exp` and one mask pass where the two kernels make seven, two and
    two. Its price: dq leaves it once an OUTER kv block, as
    Sk // block_kv_dkv copies of q that are summed after. Two kernels
    stay where the one is the wrong tool:
    - under a window (`LocalMask`): the one kernel's grid is not shrunk
      to the band and a wide outer block covers many times the band;
    - where one kv head's copies alone would hold more than
      `_ONE_KERNEL_MAX_PARTIAL_BYTES`, or not even the compute block
      fits the VMEM count."""
    B, Hq, Sq, D = q_shape
    bq, bk = _splash_blocks(Sq, Sk, D, blocks)
    two = SplashBackward("two_kernels", bk, 0, 0, kv_heads)
    if window is not None:
        return two
    itemsize = jnp.dtype(dtype).itemsize
    widest = max(Sk // _CAUSAL_OUTER_SHARE, bk) if causal else Sk
    for heads in (h for h in range(kv_heads, 0, -1) if kv_heads % h == 0):
        fits = [c for c in _OUTER_KV_BLOCKS + (bk,)
                if bk <= c <= widest and c % bk == 0 and Sk % c == 0
                and _one_kernel_vmem_bytes(bq, c, D, v_dim, itemsize,
                                           B * heads > 1)
                <= _SCOPED_VMEM_BYTES]
        if not fits:
            continue
        outer = max(fits)
        partials = Sk // outer
        partial_bytes = (partials * B * heads * (Hq // kv_heads) * Sq
                         * _lanes(D) * itemsize)
        if partial_bytes <= _ONE_KERNEL_MAX_PARTIAL_BYTES:
            return SplashBackward("one_kernel", outer, partials,
                                  partial_bytes, heads)
    return two


def _splash_block_sizes(Sq, Sk, D, blocks, backward):
    """The library's BlockSizes: forward and compute blocks as
    `_splash_blocks` gives them, the backward in the form `backward`
    (a `SplashBackward`) says."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    bq, bk = _splash_blocks(Sq, Sk, D, blocks)
    common = dict(block_q=bq, block_kv=bk, block_kv_compute=bk,
                  block_q_dkv=bq, block_kv_dkv_compute=bk)
    if backward.form == "one_kernel":
        return sk.BlockSizes(block_kv_dkv=backward.block_kv_dkv,
                             use_fused_bwd_kernel=True, **common)
    return sk.BlockSizes(block_kv_dkv=bk, block_q_dq=bq, block_kv_dq=bk,
                         **common)


def _splash_gqa(qt, kt, vt, causal, scale, padding_mask, interpret=False,
                blocks=None, segments=None, window=None):
    """GQA via splash MQA mode: qt [B, Hq, Sq, D], kt [B, Hk, Sk, D], vt
    [B, Hk, Sk, Dv]. No kv repeat materializes; the group dim rides the
    kernel's q-head axis (is_mqa=True shares one kv head across it).
    `segments` overrides the padding-mask-derived segment ids with
    explicit (q_seg [B, Sq], kv_seg [B, Sk]) int32 arrays — the
    packed-varlen route. `window` (causal only): a query sees its own
    position and the window - 1 before it."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)

    B, Hq, Sq, D = qt.shape
    Hk, Sk = kt.shape[1], kt.shape[2]
    group = Hq // Hk
    if window is not None:
        mask_cls = sm.LocalMask((Sq, Sk), (window - 1, 0), 0)
    else:
        mask_cls = (sm.CausalMask((Sq, Sk)) if causal
                    else sm.FullMask((Sq, Sk)))
    mask = sm.MultiHeadMask([mask_cls] * group)
    backward = splash_backward(qt.shape, Hk, Sk, vt.shape[-1], qt.dtype,
                               causal, window, blocks)
    kernel = sk.make_splash_mqa_single_device(
        mask, block_sizes=_splash_block_sizes(Sq, Sk, D, blocks, backward),
        residual_checkpoint_name=SPLASH_RESIDUALS, interpret=interpret)
    # splash takes pre-scaled q and no sm_scale argument
    qg = (qt * scale).reshape(B, Hk, group, Sq, D)
    seg = None
    if segments is not None:
        seg = sk.SegmentIds(q=segments[0].astype(jnp.int32),
                            kv=segments[1].astype(jnp.int32))
    elif padding_mask is not None:
        kv_seg = jnp.where(padding_mask.astype(bool), 1, 0).astype(jnp.int32)
        q_seg = kv_seg if Sq == Sk else jnp.ones((B, Sq), jnp.int32)
        seg = sk.SegmentIds(q=q_seg, kv=kv_seg)
    # vmap over batch, then kv heads (q grouped per kv head)
    run = jax.vmap(  # batch
        jax.vmap(kernel, in_axes=(0, 0, 0, None)),  # kv heads
        in_axes=(0, 0, 0, 0))
    heads = backward.kv_heads_a_call
    if heads == Hk:
        out = run(qg, kt, vt, seg)  # [B, Hk, group, Sq, Dv]
    else:
        # a few kv heads a kernel call, the calls one after the other:
        # only one call's copies of dq are live in the backward
        def turns(a):
            return jnp.moveaxis(
                a.reshape(B, Hk // heads, heads, *a.shape[2:]), 1, 0)

        out = jax.lax.map(lambda qkv: run(*qkv, seg),
                          (turns(qg), turns(kt), turns(vt)))
        out = jnp.moveaxis(out, 0, 1)
    return out.reshape(B, Hq, Sq, vt.shape[-1])


_NEG = -1e30


def _bias_chunk(kind, params, pos_q, pos_k, B, H, causal, padding_mask):
    """[B, H, len(pos_q), len(pos_k)] f32 bias chunk generated ON THE FLY
    (never the full [B, H, Sq, Sk]):

    - "alibi":    params = slopes [H]; bias = -slope * (i - j) on the
                  causal triangle (the standard ALiBi form), -slope*|i-j|
                  when not causal.
    - "rel_table": params = (table [H, 2R+1], R); bias = table[h,
                  clip(j - i, -R, R) + R] — T5-style learned relative
                  position bias, differentiable through the gather.
    - "dense":    params = array broadcastable to [B, H, Sq, Sk]; the
                  chunk is SLICED from it, so only narrow inputs (e.g.
                  [B, 1, 1, Sk]) stay narrow; a caller-materialized
                  [Sq, Sk] bias is already the caller's footprint.

    Causal and per-batch padding masks fold in as _NEG entries (the
    block-stats kernel zeroes them exactly)."""
    lq, lk = pos_q.shape[0], pos_k.shape[0]
    if kind == "alibi":
        slopes = params.astype(jnp.float32).reshape(-1)
        dist = (pos_q[:, None] - pos_k[None, :]).astype(jnp.float32)
        if not causal:
            dist = jnp.abs(dist)
        bias = -slopes[:, None, None] * dist                # [H, lq, lk]
        bias = jnp.broadcast_to(bias[None], (B, H, lq, lk))
    elif kind == "rel_table":
        table, R = params
        idx = jnp.clip(pos_k[None, :] - pos_q[:, None], -R, R) + R
        bias = jnp.take(table.astype(jnp.float32), idx,
                        axis=1)                             # [H, lq, lk]
        bias = jnp.broadcast_to(bias[None], (B, H, lq, lk))
    elif kind == "dense":
        arr = params.astype(jnp.float32)
        while arr.ndim < 4:
            arr = arr[None]
        sl_q = arr[:, :, pos_q] if arr.shape[2] != 1 else arr
        sl = sl_q[:, :, :, pos_k] if arr.shape[3] != 1 else sl_q
        bias = jnp.broadcast_to(sl, (B, H, lq if arr.shape[2] != 1 else 1,
                                     lk if arr.shape[3] != 1 else 1))
        bias = jnp.broadcast_to(bias, (B, H, lq, lk))
    else:
        raise ValueError(f"unknown bias kind {kind!r}")
    if causal:
        bias = jnp.where(pos_q[None, None, :, None]
                         >= pos_k[None, None, None, :], bias, _NEG)
    if padding_mask is not None:
        valid = padding_mask.astype(bool)[:, None, None, pos_k]
        bias = jnp.where(valid, bias, _NEG)
    return bias


def _merge_stats(m1, l1, o1, m2, l2, o2):
    """Online-softmax merge of two unnormalized partials (the ring merge):
    m/l [B, H, Sq]; o [B, Sq, H, D]."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    a1t = jnp.swapaxes(a1, 1, 2)[..., None]
    a2t = jnp.swapaxes(a2, 1, 2)[..., None]
    o = o1 * a1t + o2 * a2t
    return m, l, o


def flash_attention_biased(q, k, v, kind, params, causal=False, scale=None,
                           padding_mask=None, chunk=None, use_pallas=None):
    """Blockwise-bias flash attention, BSHD in/out (VERDICT r3 #3a/#3c;
    ref: flash_attn_kernel.cu streams the attn bias blockwise in-kernel).

    Scans KV in `chunk`-sized slices; each chunk's bias is GENERATED (or
    sliced) on the fly and fed to the block-stats kernel
    (kernels/block_attention.py — Pallas on TPU, jnp elsewhere), partials
    merged online. Peak bias footprint is O(B*H*Sq*chunk), never
    O(B*H*Sq*Sk); GQA repeats kv per-CHUNK only (chunk-bounded, exactly
    what a fused kernel's group-shared kv block read does). The scan body
    is rematerialized so chunk biases are not saved for backward.
    """
    from .block_attention import block_attention_stats
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    group = Hq // Hk
    if chunk is None:
        from . import autotune
        hit = autotune.lookup(autotune.cache_key("chunked_bias", Sk=Sk,
                                                 D=D))
        chunk = int(hit[0]) if hit else 512
    C = min(chunk, Sk)
    n_chunks = -(-Sk // C)
    pad = n_chunks * C - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pm = (padding_mask.astype(bool) if padding_mask is not None
              else jnp.ones((B, Sk), bool))
        padding_mask = jnp.pad(pm, ((0, 0), (0, pad)))
    pos_q = jnp.arange(Sq)

    def body(carry, ci):
        m, l, o = carry
        start = ci * C
        kc = jax.lax.dynamic_slice_in_dim(k, start, C, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, C, axis=1)
        if group > 1:
            kc = jnp.broadcast_to(
                kc[:, :, :, None], (B, C, Hk, group, D)).reshape(
                    B, C, Hq, D)
            vc = jnp.broadcast_to(
                vc[:, :, :, None], (B, C, Hk, group, D)).reshape(
                    B, C, Hq, D)
        pos_k = start + jnp.arange(C)
        bias_c = _bias_chunk(kind, params, pos_q, pos_k, B, Hq, causal,
                             padding_mask)
        mc, lc, oc = block_attention_stats(q, kc, vc, None, scale, bias_c,
                                           use_pallas)
        return _merge_stats(m, l, o, mc, lc, oc), None

    m0 = jnp.full((B, Hq, Sq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    o0 = jnp.zeros((B, Sq, Hq, D), jnp.float32)
    # dynamic-slice positions must be traced for a fori-style scan; remat
    # keeps chunk biases out of the residuals
    (m, l, o), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, o0), jnp.arange(n_chunks))
    lt = jnp.swapaxes(l, 1, 2)[..., None]
    out = o / jnp.maximum(lt, 1e-30)
    return out.astype(q.dtype)


def _note_kept(batch, heads, seq, dim, dtype):
    """The set-up event `train_step.kept`, once a call of the splash
    route whose stamped residuals the remat policy armed for this trace
    keeps: the name and the bytes ONE call holds across the backward
    (out in the inputs' dtype, logsumexp float32 a row a head; a scan
    around the call stacks that many a turn)."""
    from ..framework import core
    if core.remat_keeps(SPLASH_RESIDUALS):
        from ..observability import spans
        spans.setup_event(
            "train_step.kept", kept=SPLASH_RESIDUALS,
            bytes=batch * heads * seq * (dim * jnp.dtype(dtype).itemsize + 4))


def _note_backward(q, backward):
    """The set-up event `train_step.splash_backward`, once a traced call
    of the splash route (`q` a tracer: an eager call has no program to
    describe): the form `splash_backward` chose from the call's shape and
    mask, the copies of dq that leave the one kernel and their bytes."""
    if isinstance(q, jax.core.Tracer):
        from ..observability import spans
        spans.setup_event(
            "train_step.splash_backward", form=backward.form,
            partials=backward.partials,
            partial_bytes=backward.partial_bytes,
            block_kv_dkv=backward.block_kv_dkv,
            kv_heads_a_call=backward.kv_heads_a_call)


def _through_splash(q, k, v, window):
    """Whether a call without bias takes the splash route: grouped heads,
    a window, values of another width than the keys, or heads wider than
    the 128 lanes (latent attention's 256-wide keys and values: splash's
    forward stamps out and log-sum-exp for the remat policy to keep, the
    older kernel's forward would run again in the backward)."""
    return (q.shape[2] != k.shape[2] or window is not None
            or v.shape[-1] != q.shape[-1] or q.shape[-1] > 128)


def flash_attention_bshd(q, k, v, causal=False, scale=None,
                         padding_mask=None, bias=None, interpret=False,
                         blocks=None, window=None):
    """[batch, seq, heads, dim] in/out (paddle flash_attn layout).

    padding_mask: optional [batch, kv_seq] bool/int array, True/1 = valid
    token — lowered to segment-id masking. bias: optional additive mask
    broadcastable to [batch, heads, Sq, Sk] — streamed CHUNKWISE through
    the block-stats kernel (flash_attention_biased): the f32
    [B, H, Sq, Sk] score-shaped buffer the kernel ab operand would need
    is never materialized, and narrow biases (e.g. [B, 1, 1, Sk]) are
    sliced narrow per chunk. GQA/MQA (q heads a multiple of kv heads) is
    handled without materializing a kv repeat on either route (splash-MQA
    when bias is None; per-chunk broadcast otherwise). window: a causal
    query sees its own position and the window - 1 before it; v may be
    narrower or wider than k (both without bias, through splash).
    """
    if window is not None and (bias is not None or not causal):
        raise ValueError("a window is causal and takes no bias")
    if bias is None and _through_splash(q, k, v, window):
        B, Sq, Hq, D = q.shape
        _note_kept(B, Hq, Sq, v.shape[-1], q.dtype)
        _note_backward(q, splash_backward(
            (B, Hq, Sq, D), k.shape[2], k.shape[1], v.shape[-1], q.dtype,
            causal, window, blocks))
    return _bshd(q, k, v, causal=causal, scale=scale,
                 padding_mask=padding_mask, bias=bias, interpret=interpret,
                 blocks=blocks, window=window)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "interpret", "blocks",
                              "window"))
def _bshd(q, k, v, causal, scale, padding_mask, bias, interpret, blocks,
          window=None):
    """flash_attention_bshd's body (jitted apart: the event above is noted
    once a call, a cached trace would note it once a shape)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds, flash_attention)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    if bias is not None:
        # chunked-bias route — BSHD end to end, no transposes needed
        B, Sq, Hq, D = q.shape
        Sk = k.shape[1]
        use_pallas = None
        if _on_tpu() and not (Sq % 128 == 0 and Sk % 128 == 0
                              and D % 64 == 0):
            use_pallas = False
        elif _on_tpu():
            use_pallas = True
        return flash_attention_biased(
            q, k, v, "dense", bias, causal=causal, scale=scale,
            padding_mask=padding_mask, use_pallas=use_pallas)

    qt = jnp.swapaxes(q, 1, 2)  # BHSD
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    B, Hq, Sq, D = qt.shape
    Hk, Sk = kt.shape[1], kt.shape[2]

    if _through_splash(q, k, v, window):
        out = _splash_gqa(qt, kt, vt, causal, scale, padding_mask,
                          interpret=interpret, blocks=blocks, window=window)
        return jnp.swapaxes(out, 1, 2).astype(q.dtype)

    seg = None
    if padding_mask is not None:
        kv_seg = jnp.where(padding_mask.astype(bool), 1, 0).astype(jnp.int32)
        q_seg = kv_seg if Sq == Sk else jnp.ones((B, Sq), jnp.int32)
        seg = SegmentIds(q=q_seg, kv=kv_seg)
    out = flash_attention(qt, kt, vt, segment_ids=seg, causal=causal,
                          sm_scale=scale,
                          block_sizes=_block_sizes(Sq, Sk, D, causal,
                                                   blocks))
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def sweep_block_sizes(Sq=2048, Sk=2048, D=128, H=16, B=4, causal=True,
                      kv_heads=None, dtype=jnp.bfloat16, candidates=None,
                      iters=8, resweep=False):
    """On-chip block-size sweep; winners persist in the autotune cache
    (ref: phi/kernels/autotune/cache.cc). Run from bench tooling with
    PADDLE_AUTOTUNE=1, never during training. kv_heads != H tunes the
    splash GQA route (its own cache key) — the route a GQA model will
    actually take. resweep=True re-measures over a cached winner."""
    from . import autotune

    if candidates is None:
        candidates = [(bq, bk)
                      for bq in (256, 512, 1024) if bq <= Sq
                      for bk in (256, 512, 1024) if bk <= Sk]
    Hk = kv_heads or H
    if Hk != H:
        key = autotune.cache_key("splash", Sq=Sq, Sk=Sk, D=D)
    else:
        key = autotune.cache_key("flash", Sq=Sq, Sk=Sk, D=D,
                                 causal=int(causal))
    kq = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(kq[1], (B, Sk, Hk, D), dtype)
    v = jax.random.normal(kq[2], (B, Sk, Hk, D), dtype)

    def make_fn(cand):
        bq, bk = cand
        if Sq % bq or Sk % bk:
            return None

        def body(c, _):
            # grad-through to tune fwd+bwd together (training shape);
            # blocks as a static arg forces a fresh trace per candidate
            f = lambda q_: flash_attention_bshd(
                q_, k, v, causal=causal,
                blocks=(bq, bk)).astype(jnp.float32).sum()
            return c + jax.grad(f)(q).astype(jnp.float32).sum(), None

        loop = jax.jit(lambda: jax.lax.scan(
            body, jnp.float32(0), None, length=iters)[0])
        return loop

    return autotune.autotune(
        key, candidates, make_fn,
        default=[min(512, Sq), min(512, Sk)], iters=iters,
        sweep=True if (resweep or autotune.lookup(key) is None) else None)


def packed_supported(total_q, total_k, n_heads_q, n_heads_k, D) -> bool:
    """Varlen PACKED route eligibility (ref flash_attn_varlen /
    flash_attn_unpadded kernel): the packed total length pads up to the
    128 alignment, so any total works on TPU; only head-dim rules and
    the GQA group structure (q heads a multiple of kv heads — the splash
    kernel's MQA mode carries packed GQA) gate it."""
    if not _on_tpu():
        return False
    d_ok = (D % 64 == 0) if D <= 128 else (D % 128 == 0)
    return d_ok and n_heads_q % n_heads_k == 0


def flash_attention_packed(q, k, v, seg_q, seg_kv, causal=False,
                           scale=None):
    """Packed-varlen flash attention: q/k/v [total, H, D] holding many
    sequences back-to-back; seg_q/seg_kv int32 [total] sequence ids
    (1-based; 0 = padding). Runs the flash kernel with batch 1 and
    segment-id masking — cross-sequence attention is masked by segment,
    and GLOBAL causal + segments equals per-sequence causal because
    packing preserves intra-sequence order (valid for self-attention
    layouts where q and kv share the packing). GQA/MQA (Hq a multiple of
    Hk) rides the splash kernel's MQA mode with the same segment ids —
    no kv repeat materializes (VERDICT r3 #3b; ref flash_attn_unpadded
    supports GQA, phi/kernels/gpu/flash_attn_kernel.cu).
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds, flash_attention)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    Tq, Hq, D = q.shape
    Tk, Hk = k.shape[0], k.shape[1]
    if Hq != Hk:
        # splash causal masks require square score shapes: pad q and kv
        # to the same aligned total (self-attention packings have Tq==Tk)
        T = max(Tq, Tk)
        T += (-T) % _SEQ_ALIGN
        pad_q, pad_k = T - Tq, T - Tk
    else:
        pad_q = (-Tq) % _SEQ_ALIGN
        pad_k = (-Tk) % _SEQ_ALIGN
    qp = jnp.pad(q, ((0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, pad_k), (0, 0), (0, 0)))
    sq = jnp.pad(seg_q.astype(jnp.int32), (0, pad_q))   # pad -> seg 0
    sk = jnp.pad(seg_kv.astype(jnp.int32), (0, pad_k))
    qt = jnp.swapaxes(qp, 0, 1)[None]     # [1, H, T, D]
    kt = jnp.swapaxes(kp, 0, 1)[None]
    vt = jnp.swapaxes(vp, 0, 1)[None]
    if Hq != Hk:
        _note_kept(1, Hq, qt.shape[2], D, q.dtype)
        _note_backward(q, splash_backward(qt.shape, Hk, kt.shape[2], D,
                                          q.dtype, causal, None))
        out = _splash_gqa(qt, kt, vt, causal, scale, None,
                          segments=(sq[None], sk[None]))
        out = jnp.swapaxes(out[0], 0, 1)[:Tq]
        return out.astype(q.dtype)
    out = flash_attention(
        qt, kt, vt, segment_ids=SegmentIds(q=sq[None], kv=sk[None]),
        causal=causal, sm_scale=scale,
        block_sizes=_block_sizes(qt.shape[2], kt.shape[2], D, causal))
    out = jnp.swapaxes(out[0], 0, 1)[:Tq]
    return out.astype(q.dtype)
