"""glm4_moe_lite (GLM-4.7-Flash): a pre-norm decoder whose attention is
latent (MLA) and dense causal in every layer, a leading dense SwiGLU layer
then mixtures of experts, and a multi-token-prediction module in the loss.

With x the layer's normed input, one packed sequence, positions t:

  * attention (n heads, d_n no-rope, d_r rope, d_v value, ranks r_q, r_kv):
    `models/dots3_note.py`'s `LatentAttention` of kind "causal_attention":
    the same latents, up-projections, rotary and head groups, S_t = every
    s <= t, no gate, no rescale. The head count need not divide the hidden
    size (20 heads of 192 + 64 | 256 over 2048: W_UQ and W_O have 5120
    columns and rows).
  * layer i < `first_k_dense_replace`: h + SwiGLU_intermediate(RMSNorm h);
    after: `nn.DroplessMoE` told which experts it holds: sigmoid scores
    over all of them, the top-k of score + `e_score_correction_bias`
    (`n_group` 1: nothing to limit), weights the scores' own, normalised,
    x `routed_scaling_factor`; one shared expert.
  * main loss   L_main = mean_{i <= S-2} CE(RMSNorm_f(h^L_i) W_head, t_{i+1})
  * multi-token prediction, depth 1 (`MultiTokenPredictor`):
      u_i = [RMSNorm_e(Emb t_{i+1}) ; RMSNorm_h(h^L_i)] W_EH   (h^L before
      the final norm; W_EH [2H, H]); g = one whole expert layer of its own
      over u, causal, rotary positions i;
      L_MTP = mean_{i <= S-3} CE(RMSNorm_s(g_i) W_head, t_{i+2});
    Emb and W_head are the main trunk's own leaves, so each gets gradient
    from two uses. The trunk runs at the full S (kernels keep their
    alignment): position S-1 is fed id 0, a held row's, and its label is
    masked, as S-2's is (t_S does not exist).
  * `loss` = L_main + `mtp_loss_weight` * L_MTP; with the weight 0 the
    module is not built into the step and its leaves get no gradient.

The layers are `dots3_note.py`'s (`LatentAttention`, `Dots3NoteMLP`,
`Dots3NoteDecoderLayer`: one body for both models); this file holds the
configuration, the stack and the loss.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..autograd.tape import apply_op
from ..nn.layer.layers import Layer
from ..nn.layer.moe import DroplessMoE
from ..observability.scopes import scope
from ..ops._helpers import to_tensor_like
from ..tensor import Tensor
from .dots3_note import CAUSAL, Dots3NoteDecoderLayer
from .pieces import (CausalLM, DecoderStack, RMSNorm, blocked_loss, embed,
                     moe_counters, param, rms, shifted)

__all__ = ["Glm4MoeLiteConfig", "Glm4MoeLiteModel", "Glm4MoeLiteForCausalLM",
           "glm4_moe_lite_tiny"]

_F32 = jnp.float32


@dataclass
class Glm4MoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    intermediate_size: int = 10240
    num_attention_heads: int = 20
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    rope_theta: float = 1e6
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64       # the router's outputs
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    # expert parallelism: the experts [expert_offset, + experts_held) of
    # every layer live here (None: all of them)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_rows: Optional[int] = None
    head_group: int = 5
    loss_block_rows: int = 2048
    dtype: str = "bfloat16"
    mla_rescale = False              # what `LatentAttention` asks besides

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers is 0 or 1")
        # every layer, the prediction module's among them, is of one kind
        self.layer_types = (CAUSAL,) * (self.num_hidden_layers
                                        + self.num_nextn_predict_layers)

    def attention(self, kind):
        """(heads, d_n, d_r, d_v, r_q, r_kv, theta), as
        `Dots3NoteConfig.attention` gives them."""
        return (self.num_attention_heads, self.qk_nope_head_dim,
                self.qk_rope_head_dim, self.v_head_dim, self.q_lora_rank,
                self.kv_lora_rank, float(self.rope_theta))


def glm4_moe_lite_tiny(**kw):
    """Every mechanism at widths a CPU test can afford: the dense layer 0,
    two expert layers, the prediction module; 20 heads on a hidden size
    they do not divide."""
    base = dict(vocab_size=96, hidden_size=48, num_hidden_layers=3,
                intermediate_size=40, num_attention_heads=20,
                qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8,
                q_lora_rank=16, kv_lora_rank=8, moe_intermediate_size=16,
                n_routed_experts=8, num_experts_per_tok=2, head_group=5,
                loss_block_rows=8, dtype="float32")
    base.update(kw)
    return Glm4MoeLiteConfig(**base)


class Glm4MoeLiteModel(DecoderStack):
    def __init__(self, cfg: Glm4MoeLiteConfig):
        super().__init__(cfg, Dots3NoteDecoderLayer)


class MultiTokenPredictor(Layer):
    """The prediction module of depth 1: two norms, W_EH, one expert layer
    (`layer_of(cfg, num_hidden_layers)`: the trunk's own kind of layer) and
    a final norm of its own; embedding and head are its caller's."""

    def __init__(self, cfg, layer_of=Dots3NoteDecoderLayer):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.enorm = RMSNorm(h, cfg.rms_norm_eps)
        self.hnorm = RMSNorm(h, cfg.rms_norm_eps)
        self.eh_proj = param(self, (2 * h, h), P(None, None),
                             dtype=cfg.dtype)
        self.block = layer_of(cfg, cfg.num_hidden_layers)
        self.norm = RMSNorm(h, cfg.rms_norm_eps)

    def _join(self, ids, x, emb_w, enorm_w, hnorm_w, w):
        """u = [RMSNorm_e(Emb ids) ; RMSNorm_h(x)] W_EH on [B, S]."""
        eps = self.cfg.rms_norm_eps
        e = embed(ids, emb_w, under="mtp/embed")
        with scope("mtp/proj"):
            return jnp.concatenate([rms(e, enorm_w, eps),
                                    rms(x, hnorm_w, eps)], -1) @ w

    def forward(self, next_ids, x, embed_tokens):
        """g [B, S, H] (before the module's final norm) from the trunk's
        last hidden states x and the ids one position on."""
        u = apply_op(jax.checkpoint(self._join), to_tensor_like(next_ids), x,
                     embed_tokens, self.enorm.weight, self.hnorm.weight,
                     self.eh_proj, name="mtp_join")
        with scope("mtp/block"):
            return self.block(u)[0]


class Glm4MoeLiteForCausalLM(CausalLM):
    # the trunk and the kind of layer trunk and module are made of
    stack, layer = Glm4MoeLiteModel, Dots3NoteDecoderLayer

    def __init__(self, cfg):
        super().__init__(cfg, self.stack)
        self.mtp = (MultiTokenPredictor(cfg, self.layer)
                    if cfg.num_nextn_predict_layers else None)
        # the last step's two losses, beside the one the step returns
        for name in ("main_loss", "mtp_loss"):
            self.register_buffer(name, Tensor(jnp.zeros((), _F32)))

    def losses(self, input_ids, labels):
        """(L_main, L_MTP or None): the next-token cross-entropy of the
        trunk and the module's of the token after it."""
        cfg = self.cfg
        x = self.model(input_ids, final_norm=False)
        lm = blocked_loss(cfg, x, self.model.norm.weight, self.lm_head,
                          shifted(labels))
        if self.mtp is None or not cfg.mtp_loss_weight:
            return lm, None
        from ..observability import spans
        ids = to_tensor_like(input_ids).data
        spans.setup_event(
            "mtp.module", depth=cfg.num_nextn_predict_layers,
            loss_weight=cfg.mtp_loss_weight, positions=ids.shape[1] - 2,
            shares_embedding=True, shares_head=True, block_kind=(
                "moe" if isinstance(self.mtp.block.mlp, DroplessMoE)
                else "dense"))
        nxt = jnp.concatenate(
            [ids[:, 1:], jnp.zeros((ids.shape[0], 1), ids.dtype)], axis=1)
        g = self.mtp(nxt, x, self.model.embed_tokens)
        return lm, blocked_loss(
            cfg, g, self.mtp.norm.weight, self.lm_head, shifted(labels, 2),
            name="mtp_head_loss", scopes=("mtp/head", "mtp/loss"))

    def loss(self, input_ids, labels):
        """L_main + `mtp_loss_weight` * L_MTP."""
        lm, extra = self.losses(input_ids, labels)
        self.main_loss.data = lm.data.astype(_F32)
        if extra is None:
            return lm
        self.mtp_loss.data = extra.data.astype(_F32)
        w = self.cfg.mtp_loss_weight

        def total(lm_, extra_):
            with scope("mtp/loss"):
                return lm_ + w * extra_.astype(lm_.dtype)

        return apply_op(total, lm, extra, name="total_loss")

    def moe_counters(self):
        """`pieces.moe_counters` of the expert layers, the module's last."""
        return moe_counters(list(self.model.layers) + (
            [self.mtp.block] if self.mtp is not None else []))
