"""Granite-4.0-H (`granitemoehybrid`): a hybrid decoder whose mixers are
of two kinds in a fixed pattern (`layer_types`), each layer followed by a
dense SwiGLU MLP, with muP-style multipliers on the embedding, every
residual branch, the attention scores and the logits, and ONE matrix
that is both embedding and head.

  * "mamba" layers: Mamba-2. [z | xBC | dt] = u W_in; xBC through a
    short causal depthwise convolution WITH bias and SiLU
    (`kernels/short_conv.py`: `conv_bias_silu`, one kernel a pass), split
    x | B | C; dt = softplus(dt + dt_bias), A = -exp(A_log) a head; the
    chunked state-space operator of `kernels/ssd.py` (one B and C for
    all heads: `mamba_n_groups` 1) with its D skip; then the gated norm
    rms_w(y * silu(z)) over the whole inner vector (the gate BEFORE the
    norm) and the output projection
  * "attention" layers: causal softmax attention over grouped-query
    heads WITHOUT rotary embedding, scores scaled by
    `attention_multiplier` (not 1 / sqrt(d)) (`kernels/flash_attention.py`)
  * every layer: h = x + r * mixer(rms(x)); y = h + r * mlp(rms(h)), r
    the `residual_multiplier`; embeddings times `embedding_multiplier`,
    logits divided by `logits_scaling`

No multiplier is folded into a weight: each scales an activation where
the equations have it, so AdamW updates what the published model stores.
The equations are written out in `chipbench/reference_granitemoehybrid.py`,
which the tests hold this file to.

Memory at long sequences decides the structure, as in
`models/solar_open2.py`: each half of a layer (residual + mixer, residual
+ MLP) is ONE taped operation whose backward recomputes it
(`jax.checkpoint`), so only the two halves' inputs are kept a layer. A
Mamba mixer's widest tensors ([tokens, 8512] at the published widths)
fit whole, so it does not go over its heads in groups. The tied head
and the cross-entropy go over the rows in blocks
(`F.linear_cross_entropy`'s body, the table multiplied as it is stored),
a block's gradients made beside its loss from the one set of logits;
only the last norm in front of them is recomputed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..autograd.tape import apply_op
from ..framework import core
from ..nn import initializer as I
from ..nn.layer.layers import Layer
from ..observability.scopes import scope
from ..ops._helpers import to_tensor_like
from .pieces import (DecoderStack, RMSNorm, SwiGLUHalf, blocked_loss, branch,
                     param, rms, sdpa, shifted)

__all__ = ["GraniteHybridConfig", "GraniteHybridModel",
           "GraniteHybridForCausalLM", "granite_hybrid_tiny"]


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192      # `shared_intermediate_size`
    num_hidden_layers: int = 40
    layer_types: Optional[Tuple[str, ...]] = None    # None: attention at
    num_attention_heads: int = 32                    # 5, 15, 25, ..
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    loss_block_rows: int = 1024
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                "attention" if i % 10 == 5 else "mamba"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names another depth than "
                             "num_hidden_layers")
        if self.mamba_n_groups != 1:
            raise ValueError("kernels/ssd.py shares one B and C among all "
                             "heads: mamba_n_groups must be 1")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head


def granite_hybrid_tiny(**kw):
    """Every mechanism at widths a CPU test can afford."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=48,
                num_hidden_layers=4,
                layer_types=("mamba", "mamba", "attention", "mamba"),
                num_attention_heads=4, num_key_value_heads=2,
                mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
                mamba_chunk_size=8, loss_block_rows=8, dtype="float32")
    base.update(kw)
    return GraniteHybridConfig(**base)


# -- the state-space layer -----------------------------------------------------

class MambaMixer(Layer):
    """x + r * (the Mamba-2 mixer of RMSNorm(x)); see the module
    docstring."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        h, dt = cfg.hidden_size, cfg.dtype
        nh, n, inner = cfg.mamba_n_heads, cfg.mamba_d_state, cfg.mamba_d_inner
        conv = inner + 2 * n
        self.in_proj = param(self, (h, inner + conv + nh), P(None, "mp"),
                             dtype=dt)
        bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
        self.conv_weight = param(self, (cfg.mamba_d_conv, conv),
                                 P(None, "mp"),
                                 init=I.Uniform(-bound, bound), dtype=dt)
        self.conv_bias = param(self, (conv,), P(None), dtype=dt)
        # a decay of exp(-A dt) a token: A in (1, 16), dt in (1e-3, 1e-1),
        # so heads remember from a few tokens to a few thousand. These
        # three and the norm's weight stay float32 beside bf16 matrices
        self.A_log = param(self, (nh,), P(None), init=I.Uniform(1.0, 16.0),
                           dtype="float32")
        self.A_log.data = jnp.log(self.A_log.data)
        self.dt_bias = param(
            self, (nh,), P(None),
            init=I.Uniform(math.log(1e-3), math.log(1e-1)), dtype="float32")
        step = jnp.exp(self.dt_bias.data)
        self.dt_bias.data = step + jnp.log(-jnp.expm1(-step))
        self.D = param(self, (nh,), P(None), init=I.Constant(1.0),
                       dtype="float32")
        self.norm = RMSNorm(inner, cfg.rms_norm_eps)
        self.out_proj = param(self, (inner, h), P("mp", None), dtype=dt)

    def block(self, x, ln_w, w_in, w_conv, b_conv, a_log, dt_bias, d_skip,
              norm_w, w_out):
        from ..kernels.short_conv import conv_bias_silu
        from ..kernels.ssd import chunk_cumsum, ssd_chunk_scan
        cfg = self.cfg
        nh, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        inner, f32 = nh * p, jnp.float32
        B, T, _ = x.shape
        xn = rms(x, ln_w, cfg.rms_norm_eps)
        with scope("ssm/proj"):
            # a product a part: a slice of ONE wide product's output
            # would be a copy of it
            z = xn @ w_in[:, :inner]
            pre = xn @ w_in[:, inner:2 * inner + 2 * n]
            dt = jnp.matmul(xn, w_in[:, 2 * inner + 2 * n:],
                            preferred_element_type=f32)
        with scope("ssm/conv"):
            xs, Bm, Cm = conv_bias_silu(pre, w_conv, b_conv, (inner, n, n))
        with scope("ssm/dt"):
            dt = jax.nn.softplus(dt + dt_bias)
            G = chunk_cumsum(dt, -jnp.exp(a_log.astype(f32)),
                             cfg.mamba_chunk_size)
        with scope("ssm/core"):
            y = ssd_chunk_scan(xs.reshape(B, T, nh, p), dt, G, Bm, Cm, d_skip,
                               chunk=cfg.mamba_chunk_size)
        with scope("ssm/norm"):
            g = y.reshape(B, T, inner).astype(f32) * jax.nn.silu(
                z.astype(f32))
            g = (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                                   + cfg.rms_norm_eps) * norm_w
                 ).astype(x.dtype)
        with scope("ssm/out"):
            return branch(x, jnp.matmul(g, w_out, preferred_element_type=f32),
                          cfg.residual_multiplier)

    def forward(self, x, ln_w):
        return apply_op(
            jax.checkpoint(self.block, policy=core.current_remat_policy()),
            to_tensor_like(x), ln_w, self.in_proj, self.conv_weight,
            self.conv_bias, self.A_log, self.dt_bias, self.D,
            self.norm.weight, self.out_proj, name="mamba_mixer")


# -- the softmax layer ---------------------------------------------------------

class GraniteAttention(Layer):
    """x + r * Wo[causal softmax(q k^T * attention_multiplier) v], no
    rotary, no bias."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        nh, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
        self.qkv_proj = param(self, (h, (nh + 2 * kvh) * d), P(None, "mp"),
                              dtype=cfg.dtype)
        self.o_proj = param(self, (nh * d, h), P("mp", None),
                            dtype=cfg.dtype)

    def block(self, x, ln_w, wqkv, wo):
        cfg = self.cfg
        nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        B, T, _ = x.shape
        xn = rms(x, ln_w, cfg.rms_norm_eps)
        with scope("attn/qkv"):
            q = (xn @ wqkv[:, :nh * d]).reshape(B, T, nh, d)
            k = (xn @ wqkv[:, nh * d:(nh + kvh) * d]).reshape(B, T, kvh, d)
            v = (xn @ wqkv[:, (nh + kvh) * d:]).reshape(B, T, kvh, d)
        with scope("attn/core"):
            from ..kernels import flash_attention as fa
            if fa.supported(q.shape, k.shape, True):
                o = fa.flash_attention_bshd(
                    q, k, v, causal=True, scale=cfg.attention_multiplier)
            else:
                # _sdpa divides by sqrt(d): hand it q times what is left
                rep = nh // kvh
                o = sdpa(q * (cfg.attention_multiplier * math.sqrt(d)),
                         jnp.repeat(k, rep, axis=2),
                         jnp.repeat(v, rep, axis=2))
        with scope("attn/out"):
            return branch(x, jnp.matmul(o.reshape(B, T, nh * d), wo,
                                        preferred_element_type=jnp.float32),
                          cfg.residual_multiplier)

    def forward(self, x, ln_w):
        return apply_op(
            jax.checkpoint(self.block, policy=core.current_remat_policy()),
            to_tensor_like(x), ln_w, self.qkv_proj, self.o_proj,
            name="granite_attention")


# -- the MLP, a layer, the stack, the model ------------------------------------

class GraniteMLP(SwiGLUHalf):
    """h + r * SwiGLU(RMSNorm(h)) Wd, the width `shared_intermediate_size`."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__(cfg, "granite_mlp")

    def down(self, o, wd):
        return jnp.matmul(o, wd, preferred_element_type=jnp.float32)

    def join(self, h, y):
        return branch(h, y, self.cfg.residual_multiplier)


class GraniteHybridDecoderLayer(Layer):
    def __init__(self, cfg: GraniteHybridConfig, index: int):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if cfg.layer_types[index] == "attention":
            self.self_attn = GraniteAttention(cfg)
        else:
            self.mamba = MambaMixer(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                cfg.rms_norm_eps)
        self.shared_mlp = GraniteMLP(cfg)

    def forward(self, x):
        """Two taped operations, each recomputed in the backward: only x
        and the mixer half's output are kept."""
        mixer = self.self_attn if hasattr(self, "self_attn") else self.mamba
        h = mixer(x, self.input_layernorm.weight)
        return self.shared_mlp(h, self.post_attention_layernorm.weight)


class GraniteHybridModel(DecoderStack):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__(cfg, GraniteHybridDecoderLayer,
                         cfg.embedding_multiplier)


class GraniteHybridForCausalLM(Layer):
    """The head is the embedding table (`tie_word_embeddings`): one
    parameter, whose gradient is the sum of its two uses."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = GraniteHybridModel(cfg)

    def forward(self, input_ids):
        scaling = self.cfg.logits_scaling

        def head(a, w):
            with scope("head"):
                return jnp.matmul(a, jnp.swapaxes(w, 0, 1),
                                  preferred_element_type=jnp.float32
                                  ) / scaling

        return apply_op(head, self.model(input_ids), self.model.embed_tokens,
                        name="lm_head_tied")

    def loss(self, input_ids, labels):
        """Shifted next-token cross-entropy, the head and the loss a block
        of rows at a time: the last position of a sequence has no label."""
        nxt = shifted(labels)
        return blocked_loss(
            self.cfg, self.model(input_ids, final_norm=False),
            self.model.norm.weight, self.model.embed_tokens, nxt, tied=True,
            logit_scale=1.0 / self.cfg.logits_scaling)
