"""lfm2_moe (LFM2-8B-A1B): a pre-norm decoder whose mixers are of two kinds
in a fixed pattern (`layer_types`: gated short convolutions, and grouped-
query attention whose q and k are normalised a head before rotary), the
first `num_dense_layers` layers followed by a dense SwiGLU, the others by
a sigmoid-routed mixture of experts WITHOUT a shared expert, and ONE
matrix that is both embedding and head. With H the hidden size, one
sequence x [T, H]:

  * layer l: h = x + Op_l(RMSNorm(x; operator_norm));
             y = h + FF_l(RMSNorm(h; ffn_norm)); after the last layer one
    RMSNorm (`model.norm`: the published `embedding_norm`), then the head
    = the embedding table.
  * "conv" (`ShortConv`): [B | C | X] = x W_in (W_in [H, 3H], no bias);
    u = B * X; v_t = sum_j w_j * u_{t - (L-1) + j} (w [L, H], `conv_L_cache`
    L = 3 taps, zeros before the sequence's first token, no bias, NO
    activation); y = C * v; Op = y W_out. Between the projection's output
    and the one rounding of y everything is float32
    (`kernels/short_conv.py`: `gate_conv_gate`, one kernel a pass on a
    TPU). A sequence never reads another's rows.
  * "full_attention" (`Lfm2Attention`): q = x W_q [nh heads of d], k, v =
    x W_k, x W_v [kvh heads of d], no bias; q, k <- RMSNorm over a head's
    d channels (one [d] weight each), then rotary over all d dims
    (rotate-half pairs, `rope_theta`) (`pieces.qk_norm_rope`); o = causal
    softmax(q k^T / sqrt(d)) v, nh / kvh query heads a key-value head
    (`kernels/flash_attention.py` where it takes the shape); Op = o W_o.
  * l < `num_dense_layers`: FF = (silu(a W_1) * (a W_3)) W_2, width
    `intermediate_size`. After: `nn.DroplessMoE` told which experts it
    holds: s = sigmoid(a W_r) over all `num_experts`, the top-k of
    s + `expert_bias` (a buffer: it selects only), g = s[chosen] /
    (sum s[chosen] + 1e-6) x `routed_scaling_factor`, FF = sum_k g_k
    E_k(a), E a SwiGLU of width `moe_intermediate_size`. Nothing runs
    beside the routed experts.

The equations are written out in `chipbench/reference_lfm2_moe.py`, which
the tests hold this file to. As in the other models of `pieces.py`, each
half of a layer is ONE taped operation (`lfm2_conv`, `lfm2_attention`,
`lfm2_mlp`, `moe_block`) that keeps its input and is recomputed in the
backward; the tied head and the loss go over the rows in blocks.
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
from ..nn.layer.moe import DroplessMoE
from ..observability.scopes import scope
from ..ops._helpers import to_tensor_like
from .pieces import (DecoderStack, RMSNorm, SwiGLUHalf, blocked_loss, branch,
                     dropless_moe_of, moe_counters, moe_half, param,
                     qk_norm_rope, rms, sdpa, shifted)

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM",
           "lfm2_moe_tiny"]

_F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
NORM_TOPK_EPS = 1e-6        # the chosen scores are divided by their sum + this


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168      # the leading dense layers' width
    num_hidden_layers: int = 24
    layer_types: Optional[Tuple[str, ...]] = None    # None: attention at
    num_dense_layers: int = 2                        # 2, 6, 10, 14, 18, 21
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3              # taps of the short convolution
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    moe_intermediate_size: int = 1792
    num_experts: int = 32              # the router's outputs
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # expert parallelism: the experts [expert_offset, + experts_held) of
    # every layer live here (None: all of them)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_rows: Optional[int] = None
    loss_block_rows: int = 2048
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names another depth than "
                             "num_hidden_layers")
        if set(self.layer_types) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types are {CONV!r} or {ATTENTION!r}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    # what `pieces.py` asks a configuration with experts for, under the
    # names the other expert models' files have
    rms_norm_eps = property(lambda self: self.norm_eps)
    n_routed_experts = property(lambda self: self.num_experts)
    n_shared_experts = 0


def lfm2_moe_tiny(**kw):
    """Every mechanism at widths a CPU test can afford: a dense conv layer,
    then conv and attention layers with experts."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=48,
                num_hidden_layers=4,
                layer_types=(CONV, CONV, ATTENTION, CONV),
                num_dense_layers=1, num_attention_heads=4,
                num_key_value_heads=2, moe_intermediate_size=16,
                num_experts=8, num_experts_per_tok=2, loss_block_rows=8,
                dtype="float32")
    base.update(kw)
    return Lfm2MoeConfig(**base)


# -- the two mixers ------------------------------------------------------------

class ShortConv(Layer):
    """x + W_out[C * conv(B * X)], [B | C | X] = RMSNorm(x) W_in; see the
    module docstring."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        h, taps = cfg.hidden_size, cfg.conv_L_cache
        self.in_proj = param(self, (h, 3 * h), P(None, "mp"),
                             dtype=cfg.dtype)
        bound = 1.0 / math.sqrt(taps)
        self.conv_weight = param(self, (taps, h), P(None, "mp"),
                                 init=I.Uniform(-bound, bound),
                                 dtype=cfg.dtype)
        self.out_proj = param(self, (h, h), P("mp", None), dtype=cfg.dtype)

    def block(self, x, ln_w, w_in, w_conv, w_out):
        from ..kernels.short_conv import gate_conv_gate
        xn = rms(x, ln_w, self.cfg.norm_eps)
        with scope("conv/proj"):
            bcx = xn @ w_in          # ONE array: the kernel reads its thirds
        with scope("conv/core"):
            y = gate_conv_gate(bcx, w_conv)
        with scope("conv/out"):
            return branch(x, jnp.matmul(y, w_out,
                                        preferred_element_type=_F32), 1.0)

    def forward(self, x, ln_w):
        return apply_op(
            jax.checkpoint(self.block, policy=core.current_remat_policy()),
            to_tensor_like(x), ln_w, self.in_proj, self.conv_weight,
            self.out_proj, name="lfm2_conv")


class Lfm2Attention(Layer):
    """x + W_o[causal softmax(q k^T / sqrt(d)) v], q and k normalised a
    head and rotated; no bias."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        nh, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
        self.qkv_proj = param(self, (h, (nh + 2 * kvh) * d), P(None, "mp"),
                              dtype=cfg.dtype)
        self.q_layernorm = RMSNorm(d, cfg.norm_eps)
        self.k_layernorm = RMSNorm(d, cfg.norm_eps)
        self.out_proj = param(self, (nh * d, h), P("mp", None),
                              dtype=cfg.dtype)

    def block(self, x, ln_w, wqkv, wq_n, wk_n, wo):
        cfg = self.cfg
        nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        B, T, _ = x.shape
        xn = rms(x, ln_w, cfg.norm_eps)
        with scope("attn/qkv"):
            # a product a part: a slice of ONE wide product's output would
            # be a copy of it
            q = (xn @ wqkv[:, :nh * d]).reshape(B, T, nh, d)
            k = (xn @ wqkv[:, nh * d:(nh + kvh) * d]).reshape(B, T, kvh, d)
            v = (xn @ wqkv[:, (nh + kvh) * d:]).reshape(B, T, kvh, d)
        q, k = qk_norm_rope(q, k, wq_n, wk_n, cfg.norm_eps,
                            float(cfg.rope_theta))
        with scope("attn/core"):
            from ..kernels import flash_attention as fa
            if fa.supported(q.shape, k.shape, True):
                o = fa.flash_attention_bshd(q, k, v, causal=True)
            else:
                rep = nh // kvh
                o = sdpa(q, jnp.repeat(k, rep, axis=2),
                         jnp.repeat(v, rep, axis=2))
        with scope("attn/out"):
            return branch(x, jnp.matmul(o.reshape(B, T, nh * d), wo,
                                        preferred_element_type=_F32), 1.0)

    def forward(self, x, ln_w):
        return apply_op(
            jax.checkpoint(self.block, policy=core.current_remat_policy()),
            to_tensor_like(x), ln_w, self.qkv_proj, self.q_layernorm.weight,
            self.k_layernorm.weight, self.out_proj, name="lfm2_attention")


# -- a layer, the stack, the model ---------------------------------------------

class Lfm2MoeDecoderLayer(Layer):
    def __init__(self, cfg: Lfm2MoeConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.operator_norm = RMSNorm(cfg.hidden_size, cfg.norm_eps)
        if cfg.layer_types[index] == ATTENTION:
            self.self_attn = Lfm2Attention(cfg)
        else:
            self.conv = ShortConv(cfg)
        self.ffn_norm = RMSNorm(cfg.hidden_size, cfg.norm_eps)
        if index < cfg.num_dense_layers:
            self.mlp = SwiGLUHalf(cfg, "lfm2_mlp")
        else:
            self.mlp = dropless_moe_of(cfg, selection_bias=True,
                                       norm_topk_eps=NORM_TOPK_EPS)

    def forward(self, x):
        """Two taped operations, each recomputed in the backward: only x
        and the mixer half's output are kept."""
        mixer = self.self_attn if hasattr(self, "self_attn") else self.conv
        h = mixer(x, self.operator_norm.weight)
        if isinstance(self.mlp, DroplessMoE):
            return moe_half(self.mlp, h, self.ffn_norm.weight,
                            self.cfg.norm_eps)
        return self.mlp(h, self.ffn_norm.weight)


class Lfm2MoeModel(DecoderStack):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__(cfg, Lfm2MoeDecoderLayer)


class Lfm2MoeForCausalLM(Layer):
    """The head is the embedding table: one parameter, whose gradient is
    the sum of its two uses."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Lfm2MoeModel(cfg)

    def forward(self, input_ids):
        def head(a, w):
            with scope("head"):
                return jnp.matmul(a, jnp.swapaxes(w, 0, 1),
                                  preferred_element_type=_F32)

        return apply_op(head, self.model(input_ids), self.model.embed_tokens,
                        name="lm_head_tied")

    def loss(self, input_ids, labels):
        """Shifted next-token cross-entropy, the head and the loss a block
        of rows at a time: the last position of a sequence has no label."""
        return blocked_loss(
            self.cfg, self.model(input_ids, final_norm=False),
            self.model.norm.weight, self.model.embed_tokens, shifted(labels),
            tied=True)

    def moe_counters(self):
        """`pieces.moe_counters` of the expert layers."""
        return moe_counters(self.model.layers)
