"""Rotary position embedding (ref: phi fused_rope kernel,
python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py).

Pure-jnp rotate-half formulation — XLA fuses the mul/adds into surrounding
matmuls, so a bespoke Pallas kernel buys nothing here (measured pattern on
TPU); cos/sin caches are precomputed once per (seq, dim).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Yarn(NamedTuple):
    """A rotary base under YaRN scaling (`rope_scaling.type` "yarn"), as the
    released latent-attention code of the family reads its keys: `theta`
    the base, `factor` s, `original` L0 the trained context, `beta_fast`
    and `beta_slow` the rotations at L0 between which a frequency goes from
    kept to divided by s, `mscale` and `mscale_all_dim` the two attention
    factors. Hashable: a table cache's key as a plain base is."""
    theta: float
    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(s, m):
    """0.1 m ln s + 1 (1 for s <= 1)."""
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def inv_freq(dim, theta):
    """The dim / 2 rotary frequencies, float64 on the host. A plain base:
    f_i = theta^(-2i/dim). A `Yarn`: with dim(b) = dim ln(L0 / (2 pi b)) /
    (2 ln theta), low = max(floor(dim(beta_fast)), 0), high =
    min(ceil(dim(beta_slow)), dim/2 - 1), ramp_i = clip((i - low) / (high -
    low), 0, 1): f_i (1 - ramp_i) + (f_i / s) ramp_i."""
    if not isinstance(theta, Yarn):
        return theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = theta
    f = float(y.theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    at = lambda b: (dim * math.log(y.original / (2 * math.pi * b))
                    / (2 * math.log(y.theta)))
    low = max(math.floor(at(y.beta_fast)), 0)
    high = min(math.ceil(at(y.beta_slow)), dim // 2 - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / y.factor) * ramp


def table_scale(theta):
    """What cos and sin are multiplied by: mscale(s, `mscale`) /
    mscale(s, `mscale_all_dim`) under YaRN, 1 for a plain base."""
    if not isinstance(theta, Yarn):
        return 1.0
    return (yarn_mscale(theta.factor, theta.mscale)
            / yarn_mscale(theta.factor, theta.mscale_all_dim))


def softmax_scale(theta, d):
    """The attention scores' factor for keys of width d: d^-0.5, times
    mscale(s, `mscale_all_dim`)^2 under YaRN where that key is set."""
    if isinstance(theta, Yarn) and theta.mscale_all_dim:
        return yarn_mscale(theta.factor, theta.mscale_all_dim) ** 2 \
            / math.sqrt(d)
    return 1.0 / math.sqrt(d)


@functools.lru_cache(maxsize=16)
def tables(seq_len, dim, theta):
    """cos and sin [S, dim] float32 of the rotate-half layout, angles made
    in float64 on the host (constants of the program): `_cos_sin_cache`
    rounds the angle itself to float32, 1e-3 rad at 16384 positions.
    `theta` is the base, or a `Yarn` (the frequencies and the tables'
    factor are `inv_freq` / `table_scale`)."""
    ang = np.outer(np.arange(seq_len, dtype=np.float64),
                   inv_freq(dim, theta))
    ang = np.concatenate([ang, ang], axis=-1)
    cos, sin = np.cos(ang), np.sin(ang)
    k = table_scale(theta)
    if k != 1.0:
        cos, sin = cos * k, sin * k
    return cos.astype(np.float32), sin.astype(np.float32)


def head_norm(x, w, eps):
    """A head's own RMSNorm, float32 out: x [..., heads, d] * rsqrt(mean
    over d of x^2 + eps) * w, w [d] one weight for every head. What
    follows it (`rotate`) rounds."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rotate(x, theta, dtype=None):
    """Rotary over all d dims of x [B, S, heads, d] at positions 0..S-1 by
    `tables(S, d, theta)`, float32 inside, rounded once to `dtype` (x's
    own where None)."""
    cos, sin = (t[None, :, None, :]
                for t in tables(x.shape[1], x.shape[-1], theta))
    xf = x.astype(jnp.float32)
    return (xf * cos + _rotate_half(xf) * sin).astype(dtype or x.dtype)


@functools.lru_cache(maxsize=32)
def _cos_sin_cache(seq_len: int, dim: int, base: float, dtype_str: str):
    # host-side numpy so cached values are concrete constants — caching
    # device arrays here would leak tracers when called under jit/remat
    inv_freq = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    t = np.arange(seq_len, dtype=np.float32)
    freqs = np.outer(t, inv_freq)                  # [S, dim/2]
    emb = np.concatenate([freqs, freqs], axis=-1)  # [S, dim]
    return np.cos(emb), np.sin(emb)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def fused_qkv_rope(a, w_qkv, num_heads, num_kv_heads, head_dim,
                   position_ids=None, base=10000.0, seq_len=None):
    """Fused QKV+RoPE prologue: one wide projection, then rope applied
    to the q/k slices in-register via the cos/sin cache — no separate
    narrow matmuls, no standalone elementwise pass over q and k.

    a: [B, S, H] (or [S, H] packed rows); w_qkv:
    [H, (num_heads + 2*num_kv_heads) * head_dim] with q|k|v column
    layout (LlamaAttention.qkv_proj's stored layout). Returns
    (q, k, v) shaped [..., heads, head_dim] with rope already applied
    to q and k. position_ids/seq_len follow apply_rope (packed [S]
    rows get a broadcast batch dim internally)."""
    from jax.ad_checkpoint import checkpoint_name
    nh, kvh, d = num_heads, num_kv_heads, head_dim
    qkv = checkpoint_name(a @ w_qkv, "llama_qkv")
    lead = qkv.shape[:-1]
    q = qkv[..., :nh * d].reshape(*lead, nh, d)
    k = qkv[..., nh * d:(nh + kvh) * d].reshape(*lead, kvh, d)
    v = qkv[..., (nh + kvh) * d:].reshape(*lead, kvh, d)
    if a.ndim == 2:                      # packed rows: [S, H]
        pids = None if position_ids is None else position_ids[None]
        q4, k4 = apply_rope(q[None], k[None], position_ids=pids,
                            base=base, seq_len=seq_len)
        return q4[0], k4[0], v
    q, k = apply_rope(q, k, position_ids=position_ids, base=base,
                      seq_len=seq_len)
    return q, k, v


def apply_rope(q, k, position_ids=None, base=10000.0, seq_len=None):
    """q, k: [B, S, H, D] -> rotated (same shapes), f32 math, input dtype out.

    seq_len: table length when position_ids may exceed q's length (KV-cache
    decode, where q holds 1 token at an arbitrary absolute position)."""
    S, D = q.shape[1], q.shape[-1]
    if position_ids is not None and seq_len is not None:
        S = int(seq_len)
    cos, sin = _cos_sin_cache(S, D, base, "f32")
    if position_ids is not None:
        cos = jnp.take(cos, position_ids, axis=0)  # [B, S, D]
        sin = jnp.take(sin, position_ids, axis=0)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    q_out = qf * cos + _rotate_half(qf) * sin
    k_out = kf * cos + _rotate_half(kf) * sin
    return q_out.astype(q.dtype), k_out.astype(k.dtype)
