"""xing4_0 (Xing4.0-29B-A4B): a pre-norm decoder whose RESIDUAL PATH is n
streams wide (manifold-constrained hyper-connections, arXiv:2512.24880,
after hyper-connections, arXiv:2409.19606) around latent attention (MLA,
dense causal, rotary under YaRN), leading dense SwiGLU layers then mixtures
of experts, and a multi-token-prediction module in the loss.

n = `hc_mult`, C = `hidden_size`; per token, X in R^{n x C}:

  * entry: X_0[j] = Emb(t) for every stream j.
  * a half-layer with branch F (F = Attn(RMSNorm_in(u)) or
    FFN(RMSNorm_post(u)); F contains no add), `pieces.HyperConnection`:
      x~ = vec(X) in float32, r = rsqrt(mean(x~^2) + rms_norm_eps),
      m = r (x~ Phi), Phi [nC, 2n + n^2] (the norm's weight is in Phi);
      H~_pre = a_pre m[0:n] + b_pre, H~_post = a_post m[n:2n] + b_post,
      H~_res = a_res reshape(m[2n:], n, n) + b_res;
      H_pre = sigmoid(H~_pre), H_post = 2 sigmoid(H~_post);
      M_0 = exp(clamp(H~_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max));
      `hc_sinkhorn_iters` times: M <- M / (row sums + hc_eps), then
      M <- M / (column sums + hc_eps); H_res = the last M;
      u = sum_j H_pre[j] X[j];  y = F(u);
      X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y.
    The maps are float32, the mixes accumulate in float32 and round once.
  * exit: x = sum_j X_L[j], then the final RMSNorm.
  * attention: `dots3_note.LatentAttention` of kind "causal_attention",
    no gate, no rescale; rotary on the d_r rope dims under YaRN
    (`kernels.rope.Yarn`: the frequencies of `kernels.rope.inv_freq`, cos and
    sin times mscale(s, mscale) / mscale(s, mscale_all_dim), the softmax
    scale (d_n + d_r)^-0.5 mscale(s, mscale_all_dim)^2), tables made on the
    host from float64 angles.
  * feed-forward: layer i < `first_k_dense_replace` a SwiGLU of
    `intermediate_size`; after, `nn.DroplessMoE` as `glm4_moe_lite` spells
    it (sigmoid scores over all experts, top-k of score +
    `e_score_correction_bias`, weights the scores' own, normalised, x
    `routed_scaling_factor`; one shared expert), told which experts it holds.
  * loss: L_main + `mtp_loss_weight` L_MTP, `glm4_moe_lite`'s own: the
    module reads the REDUCED trunk output h^L before the final norm; its u
    is expanded to n streams, goes through one whole n-stream expert layer
    and is reduced by the sum, then the module's norm and the shared head.

X is laid out [n, B, S, C]: a stream is one contiguous slab (the layout
the TPU compiler gives the backward's sums anyway). What a half keeps for
the backward: the attention half its X, the maps, the one-stream u and y,
the latents and the cores' out + log-sum-exp; a feed-forward half its X
alone: map, pre and the branch run again in the backward. The layers are
`dots3_note.LatentAttention`, `pieces.SwiGLUHalf` and `pieces.moe_half` on
a `pieces.HyperConnection` each; stack and loss are `glm4_moe_lite`'s.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..autograd.tape import apply_op
from ..kernels.rope import Yarn
from ..nn.layer.layers import Layer
from ..nn.layer.moe import DroplessMoE
from .dots3_note import CAUSAL, LatentAttention
from .glm4_moe_lite import Glm4MoeLiteForCausalLM
from .pieces import (DecoderStack, HyperConnection, RMSNorm, SwiGLUHalf,
                     dropless_moe_of, expand_streams, moe_half,
                     reduce_streams)

__all__ = ["Xing40Config", "Xing40Model", "Xing40ForCausalLM",
           "xing4_0_tiny"]


@dataclass
class Xing40Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    intermediate_size: int = 9216
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    rope_theta: float = 1e4
    # rope_scaling of type "yarn": (factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale, mscale_all_dim); None: the plain base
    rope_scaling: Optional[Tuple[float, ...]] = (64.0, 4096, 32.0, 1.0, 1.0,
                                                 1.0)
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64       # the router's outputs
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    # the residual path
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # how a half-layer's path starts: (the three scales, b_pre, b_post,
    # b_res on the diagonal, b_res off it); the paper's own start
    hc_init: Tuple[float, ...] = (0.01, -1.0, 0.0, 1.0, -1.0)
    # expert parallelism: the experts [expert_offset, + experts_held) of
    # every layer live here (None: all of them)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_rows: Optional[int] = None
    head_group: int = 8
    loss_block_rows: int = 2048
    dtype: str = "bfloat16"
    mla_rescale = False              # what `LatentAttention` asks besides

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers is 0 or 1")
        if self.hc_mult < 2:
            raise ValueError("hc_mult < 2 is the plain residual path: "
                             "glm4_moe_lite")
        self.layer_types = (CAUSAL,) * (self.num_hidden_layers
                                        + self.num_nextn_predict_layers)

    def rotary(self):
        """The rotary base as `LatentAttention` reads it."""
        if self.rope_scaling is None:
            return float(self.rope_theta)
        return Yarn(float(self.rope_theta), *self.rope_scaling)

    def attention(self, kind):
        """(heads, d_n, d_r, d_v, r_q, r_kv, rotary base), as
        `Dots3NoteConfig.attention` gives them."""
        return (self.num_attention_heads, self.qk_nope_head_dim,
                self.qk_rope_head_dim, self.v_head_dim, self.q_lora_rank,
                self.kv_lora_rank, self.rotary())


def xing4_0_tiny(**kw):
    """Every mechanism at widths a CPU test can afford: four streams, the
    dense layer 0, one expert layer, the prediction module, YaRN whose
    ramp lies inside the rope dims; the dynamic term of the maps scaled up
    (hidden 48 gives m a tenth of the spread hidden 3584 does) and four
    Sinkhorn iterations (a test's seconds are the operations it compiles)."""
    base = dict(vocab_size=96, hidden_size=48, num_hidden_layers=2,
                first_k_dense_replace=1, intermediate_size=40,
                num_attention_heads=4, qk_nope_head_dim=6,
                qk_rope_head_dim=4, v_head_dim=8, q_lora_rank=16,
                kv_lora_rank=8, rope_scaling=(64.0, 16, 32.0, 1.0, 1.0, 1.0),
                moe_intermediate_size=16, n_routed_experts=8,
                num_experts_per_tok=2, head_group=2, loss_block_rows=8,
                hc_init=(2.0, -1.0, 0.0, 1.0, -1.0), hc_sinkhorn_iters=4,
                dtype="float32")
    base.update(kw)
    return Xing40Config(**base)


class Xing40DecoderLayer(Layer):
    """Two half-layers, each on a `HyperConnection` of its own. Layer
    `num_hidden_layers` is the prediction module's: fed ONE stream, it
    expands it and reduces what it made."""

    def __init__(self, cfg: Xing40Config, index: int):
        super().__init__()
        self.cfg = cfg
        self.of_module = index >= cfg.num_hidden_layers
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LatentAttention(cfg, CAUSAL)
        self.attn_hc = HyperConnection(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                cfg.rms_norm_eps)
        if index < cfg.first_k_dense_replace:
            self.mlp = SwiGLUHalf(cfg, "xing4_0_mlp")
        else:
            self.mlp = dropless_moe_of(cfg, selection_bias=True)
        self.mlp_hc = HyperConnection(cfg)

    def forward(self, x):
        """(y, None): X [n, B, S, H] -> X' (the module's layer: [B, S, H]
        -> [B, S, H]). Two taped operations, see the module docstring."""
        if self.of_module:
            x = apply_op(expand_streams, x, name="hc_expand",
                         n=self.cfg.hc_mult)
        h, _ = self.self_attn(x, self.input_layernorm.weight,
                              path=self.attn_hc)
        ln_w = self.post_attention_layernorm.weight
        if isinstance(self.mlp, DroplessMoE):
            y = moe_half(self.mlp, h, ln_w, self.cfg.rms_norm_eps,
                         path=self.mlp_hc)
        else:
            y = self.mlp(h, ln_w, path=self.mlp_hc)
        if self.of_module:
            y = apply_op(reduce_streams, y, name="hc_reduce")
        return y, None


class Xing40Model(DecoderStack):
    def __init__(self, cfg: Xing40Config):
        super().__init__(cfg, Xing40DecoderLayer, streams=cfg.hc_mult)


class Xing40ForCausalLM(Glm4MoeLiteForCausalLM):
    """`glm4_moe_lite`'s loss and module over the four-stream stack."""

    stack, layer = Xing40Model, Xing40DecoderLayer

    def blocks(self):
        return list(self.model.layers) + (
            [self.mtp.block] if self.mtp is not None else [])

    def losses(self, input_ids, labels):
        from ..observability import spans
        cfg = self.cfg
        ids = np.shape(getattr(input_ids, "data", input_ids))
        one = (ids[0] * ids[1] * cfg.hidden_size
               * jnp.dtype(cfg.dtype).itemsize)
        spans.setup_event(
            "hc.streams", n=cfg.hc_mult, iterations=cfg.hc_sinkhorn_iters,
            halves=2 * len(self.blocks()),
            stream_array_bytes=cfg.hc_mult * one,
            attention_half_keeps="X + maps + u + y + latents + core out/lse",
            ffn_half_keeps="X", kept_one_stream_bytes=one)
        return super().losses(input_ids, labels)

    def hc_counters(self):
        """{"res_sum_err": [largest |row sum - 1|, largest |column sum - 1|]
        of H_res over the last step's tokens and half-layers} (host numbers;
        reading waits for the device)."""
        errs = np.stack([np.asarray(hc.res_sum_err.data)
                         for b in self.blocks()
                         for hc in (b.attn_hc, b.mlp_hc)])
        return {"res_sum_err": errs.max(axis=0).tolist()}
