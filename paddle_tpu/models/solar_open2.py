"""Solar-Open2: a hybrid decoder whose layers are of two kinds in a fixed
pattern, each followed by a mixture of experts.

  * `gqa_layers` (every fourth): causal softmax attention over
    grouped-query heads WITHOUT rotary embedding, its output gated per
    channel by sigmoid(x Wg) before the output projection
    (`kernels/flash_attention.py`)
  * every other layer: gated delta-rule linear attention ("KDA"): a
    4-tap causal depthwise convolution and SiLU on q, k, v, L2-normalised
    q and k (`kernels/short_conv.py`: one kernel a pass), a per-channel
    decay from a low-rank projection, beta in (0, 2), the chunked
    operator of `kernels/gated_delta_rule.py`, a per-head RMSNorm and a
    low-rank sigmoid gate
  * every layer's feed-forward: `nn.DroplessMoE`, sigmoid-routed top-k
    over all the router's experts, computing the experts this model is
    TOLD it holds (`experts_held` from `expert_offset`) plus one shared
    expert

The equations are written out in `tests/reference/solar_open2.py`, which
the tests hold this file to.

Memory at long sequences decides the structure. Each half of a layer
(residual + mixer, residual + experts) is ONE taped operation that keeps
its input alone; its backward recomputes it, a mixer's a group of heads at
a time (`jax.checkpoint`). The softmax mixer sums its groups' projected
parts (one KV head with its query heads). The delta-rule mixer norms and
down-projects once a layer, scans `kda_head_group` heads a group and
projects the groups' stacked outputs at once: one [tokens, heads x dim]
array a layer where a float32 [tokens, hidden] sum was. Head and loss go
over blocks of rows, a block's gradients made beside its loss (norm redone).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..autograd.tape import apply_op
from ..nn import initializer as I
from ..nn.layer.layers import Layer
from ..observability.scopes import scope
from ..ops._helpers import to_tensor_like
from .pieces import (CausalLM, DecoderStack, RMSNorm, blocked_loss,
                     dropless_moe_of,
                     group_of, moe_counters, moe_half, param, rms, sdpa,
                     shifted, sum_of_groups)

__all__ = ["SolarOpen2Config", "SolarOpen2Model", "SolarOpen2ForCausalLM",
           "solar_open2_tiny"]


@dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    gqa_layers: Optional[Tuple[int, ...]] = None   # None: 0, 4, 8, ...
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_low_rank: int = 128          # of the decay and of the output gate
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320      # the router's outputs
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    # expert parallelism: the experts [expert_offset, + experts_held) of
    # every layer live here (None: all of them)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    # rows of an expert layer's buffer (None: every pair could come here)
    moe_rows: Optional[int] = None
    kda_chunk: int = 64
    kda_head_group: int = 4
    loss_block_rows: int = 2048
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.gqa_layers is None:
            self.gqa_layers = tuple(range(0, self.num_hidden_layers, 4))
        self.gqa_layers = tuple(self.gqa_layers)


def solar_open2_tiny(**kw):
    """Every mechanism at widths a CPU test can afford."""
    base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                linear_num_heads=4, linear_head_dim=8, kda_low_rank=4,
                moe_intermediate_size=16, n_routed_experts=8,
                num_experts_per_tok=2, kda_chunk=16, kda_head_group=2,
                loss_block_rows=8, dtype="float32")
    base.update(kw)
    return SolarOpen2Config(**base)


# -- the softmax layer ---------------------------------------------------------

class GatedAttention(Layer):
    """x + Wo[(causal softmax attention, no rotary) * sigmoid(x Wg)]."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        nh, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
        self.qkv_proj = param(self, (h, (nh + 2 * kvh) * d), P(None, "mp"),
                              dtype=cfg.dtype)
        self.gate_proj = param(self, (h, nh * d), P(None, "mp"),
                               dtype=cfg.dtype)
        self.o_proj = param(self, (nh * d, h), P("mp", None),
                            dtype=cfg.dtype)

    def _group(self, g, x, ln_w, wqkv, wg, wo):
        """KV head g with its query heads: their part of the output
        projection, [B, T, H] float32 (the groups' parts are summed)."""
        cfg = self.cfg
        B, T, h = x.shape
        nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        rep = nh // kvh
        xn = rms(x, ln_w, cfg.rms_norm_eps)
        with scope("attn/qkv"):
            q = (xn @ group_of(wqkv[:, :nh * d], 1, kvh, g)).reshape(
                B, T, rep, d)
            k = (xn @ group_of(wqkv[:, nh * d:(nh + kvh) * d], 1, kvh,
                               g)).reshape(B, T, 1, d)
            v = (xn @ group_of(wqkv[:, (nh + kvh) * d:], 1, kvh,
                               g)).reshape(B, T, 1, d)
        with scope("attn/core"):
            from ..kernels import flash_attention as fa
            if fa.supported(q.shape, k.shape, True):
                o = fa.flash_attention_bshd(q, k, v, causal=True)
            else:
                o = sdpa(q, jnp.repeat(k, rep, axis=2),
                         jnp.repeat(v, rep, axis=2))
        with scope("attn/gate"):
            gate = jax.nn.sigmoid(
                (xn @ group_of(wg, 1, kvh, g)).astype(jnp.float32))
            o = (o.reshape(B, T, rep * d).astype(jnp.float32)
                 * gate).astype(x.dtype)
        with scope("attn/out"):
            wo_g = jax.lax.dynamic_index_in_dim(
                wo.reshape(kvh, rep * d, h), g, 0, keepdims=False)
            return jnp.matmul(o, wo_g, preferred_element_type=jnp.float32)

    def block(self, x, *ws):
        mixed = sum_of_groups(self._group, self.cfg.num_key_value_heads,
                              x, ws)
        with scope("attn/out"):
            return x + mixed

    def forward(self, x, ln_w):
        return apply_op(self.block, to_tensor_like(x), ln_w, self.qkv_proj,
                        self.gate_proj, self.o_proj, name="gated_attention")


# -- the linear-attention layer ------------------------------------------------

class KDAttention(Layer):
    """x + the gated delta-rule mixer of RMSNorm(x). What is of hidden
    width and the same for every group of heads runs once a layer, around
    the scan over the groups; the block has a backward of its own
    (`_backward`) that keeps of the forward the layer's input alone."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        h, r = cfg.hidden_size, cfg.kda_low_rank
        nl, dl = cfg.linear_num_heads, cfg.linear_head_dim
        dt = cfg.dtype
        self.qkv_proj = param(self, (h, 3 * nl * dl), P(None, "mp"),
                              dtype=dt)
        self.conv_weight = param(
            self, (cfg.short_conv_kernel_size, 3 * nl * dl), P(None, "mp"),
            init=I.Uniform(-0.5, 0.5), dtype=dt)
        self.decay_down = param(self, (h, r), P(None, None), dtype=dt)
        self.decay_up = param(self, (r, nl * dl), P(None, "mp"), dtype=dt)
        # a decay of exp(-A dt) a token: A in (1, 16), dt in (1e-3, 1e-1),
        # so heads remember from a few tokens to a few thousand
        self.A_log = param(self, (nl,), P(None), init=I.Uniform(1.0, 16.0),
                           dtype="float32")
        self.A_log.data = jnp.log(self.A_log.data)
        self.dt_bias = param(
            self, (nl * dl,), P(None),
            init=I.Uniform(math.log(1e-3), math.log(1e-1)), dtype="float32")
        step = jnp.exp(self.dt_bias.data)
        self.dt_bias.data = step + jnp.log(-jnp.expm1(-step))
        self.beta_proj = param(self, (h, nl), P(None, "mp"), dtype=dt)
        self.gate_down = param(self, (h, r), P(None, None), dtype=dt)
        self.gate_up = param(self, (r, nl * dl), P(None, "mp"), dtype=dt)
        self.o_norm = RMSNorm(dl, cfg.rms_norm_eps)
        self.o_proj = param(self, (nl * dl, h), P("mp", None), dtype=dt)

    def _groups(self):
        nl, hg = self.cfg.linear_num_heads, self.cfg.kda_head_group
        return nl // hg if nl % hg == 0 else 1

    def _shared(self, x, ln_w, wdd, wgd, wbeta):
        """Once a layer: (RMSNorm(x), xn @ [decay_down | gate_down |
        beta_proj]), the second [B, T, 2 rank + heads]."""
        xn = rms(x, ln_w, self.cfg.rms_norm_eps)
        with scope("kda/gate"):
            return xn, xn @ jnp.concatenate([wdd, wgd, wbeta], axis=1)

    def _of_group(self, g, low, wqkv, wconv, wdu, a_log, dt_bias, wgu, wo):
        """What group g takes of the shared columns and of the weights
        that have a head axis."""
        G, r = self._groups(), self.cfg.kda_low_rank
        hg = self.cfg.linear_num_heads // G
        return (low[..., :r], low[..., r:2 * r],
                jax.lax.dynamic_slice_in_dim(low, 2 * r + g * hg, hg, -1),
                group_of(wqkv, 3, G, g), group_of(wconv, 3, G, g),
                group_of(wdu, 1, G, g), group_of(a_log[None], 1, G, g)[0],
                group_of(dt_bias[None], 1, G, g)[0],
                group_of(wgu, 1, G, g),
                jax.lax.dynamic_index_in_dim(
                    wo.reshape(G, -1, wo.shape[-1]), g, 0, keepdims=False))

    def _group(self, xn, dd, gd, b, wqkv, wconv, wdu, a_log, dt_bias, wgu,
               o_norm_w):
        """One group's heads, gated and normed: [B, T, hg * dl] in xn's
        dtype. dd, gd [B, T, rank] and b [B, T, hg] are its columns of the
        shared product, the weights its own slices."""
        from ..kernels.gated_delta_rule import chunk_gated_delta_rule
        from ..kernels.short_conv import conv_silu_l2norm
        cfg = self.cfg
        dl = cfg.linear_head_dim
        B, T, hg = b.shape
        f32 = jnp.float32
        with scope("kda/proj"):
            pre = xn @ wqkv                              # [B, T, 3 hg dl]
        with scope("kda/conv"):
            q, k, v = conv_silu_l2norm(pre, wconv, hg)
        with scope("kda/gate"):
            soft = jax.nn.softplus((dd @ wdu).astype(f32) + dt_bias)
            decay = (-jnp.exp(a_log.astype(f32))[:, None]
                     * soft.reshape(B, T, hg, dl))
            beta = 2.0 * jax.nn.sigmoid(b.astype(f32))
        with scope("kda/core"):
            o = chunk_gated_delta_rule(q, k, v, decay, beta,
                                       chunk=cfg.kda_chunk)
        with scope("kda/out"):
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + cfg.rms_norm_eps) * o_norm_w
            gate = jax.nn.sigmoid((gd @ wgu).astype(f32))
            return (o.reshape(B, T, hg * dl) * gate).astype(xn.dtype)

    def _projected(self, wo, *args):
        """A group's part of the output projection, for the backward: its
        cotangent is the layer's own."""
        o = self._group(*args)
        with scope("kda/out"):
            return o @ wo

    def _mix(self, x, ln_w, wqkv, wconv, wdd, wdu, a_log, dt_bias, wbeta,
             wgd, wgu, o_norm_w, wo):
        """The block: the shared part, the groups' heads stacked by a scan,
        ONE output projection over them (float32 sums), the residual."""
        G = self._groups()
        xn, low = self._shared(x, ln_w, wdd, wgd, wbeta)

        def body(_, g):
            *mine, _ = self._of_group(g, low, wqkv, wconv, wdu, a_log,
                                      dt_bias, wgu, wo)
            return None, self._group(xn, *mine, o_norm_w)

        _, o = jax.lax.scan(body, None, jnp.arange(G))   # [G, B, T, hg dl]
        with scope("kda/out"):
            mixed = jax.lax.dot_general(
                o, wo.reshape(G, -1, wo.shape[-1]),
                (((0, 3), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32)
            return x + mixed.astype(x.dtype)

    def _backward(self, saved, dy):
        """The layer's input and weights -> every cotangent. The shared
        part is recomputed once, then a group at a time: jax's own
        backward of the checkpointed group with its part of the output
        projection (the recomputed forward and the transposed operations
        carry jax's marks), the cotangents of what the groups share summed
        in float32, a group's own stacked by the scan."""
        # behind the cotangent, as jax.checkpoint puts what it recomputes:
        # XLA would share this pass's norm and weight layouts with the
        # forward's and keep them alive from there to here
        saved, dy = jax.lax.optimization_barrier((saved, dy))
        x, ln_w, wqkv, wconv, wdd, wdu, a_log, dt_bias, wbeta, wgd, wgu, \
            o_norm_w, wo = saved
        r = self.cfg.kda_low_rank
        f32 = jnp.float32
        (xn, low), shared_vjp = jax.vjp(self._shared, x, ln_w, wdd, wgd,
                                        wbeta)
        run = jax.checkpoint(self._projected)

        def body(sums, g):
            mine = self._of_group(g, low, wqkv, wconv, wdu, a_log, dt_bias,
                                  wgu, wo)
            _, vjp = jax.vjp(run, mine[-1], xn, *mine[:-1], o_norm_w)
            dwo, dxn, ddd, dgd, db, *dws, don = vjp(dy)
            sums = tuple(a + d.astype(f32)
                         for a, d in zip(sums, (dxn, ddd, dgd, don)))
            return sums, (db, *dws, dwo)

        G = self._groups()
        (dxn, ddd, dgd, don), (db, dwqkv, dwconv, dwdu, da, ddt, dwgu,
                               dwo) = jax.lax.scan(
            body, tuple(jnp.zeros(a.shape, f32) for a in (
                xn, low[..., :r], low[..., :r], o_norm_w)), jnp.arange(G))

        def columns(d, parts, like):
            """The groups' stacked [G, rows, parts * n] as like's
            [rows, parts * G * n]: `_group_of`'s inverse."""
            d = d.reshape(G, d.shape[1], parts, -1)
            return jnp.moveaxis(d, 0, 2).reshape(like.shape)

        dlow = jnp.concatenate(
            [ddd.astype(low.dtype), dgd.astype(low.dtype),
             jnp.moveaxis(db, 0, -2).reshape(*db.shape[1:-1], -1)], -1)
        dx, dln_w, dwdd, dwgd, dwbeta = shared_vjp(
            (dxn.astype(xn.dtype), dlow))
        return (dy + dx, dln_w, columns(dwqkv, 3, wqkv),
                columns(dwconv, 3, wconv), dwdd, columns(dwdu, 1, wdu),
                da.reshape(a_log.shape), ddt.reshape(dt_bias.shape), dwbeta,
                dwgd, columns(dwgu, 1, wgu), don.astype(o_norm_w.dtype),
                dwo.reshape(wo.shape))

    def block(self, x, *ws):
        from ..observability import spans
        cfg = self.cfg
        G = self._groups()
        spans.setup_event(
            "kda.groups", groups=G, heads_per_group=cfg.linear_num_heads // G,
            hidden_width_products_in_group=1,
            shared_columns=2 * cfg.kda_low_rank + cfg.linear_num_heads,
            stacked_out_bytes=(x.size // cfg.hidden_size * cfg.linear_num_heads
                               * cfg.linear_head_dim * x.dtype.itemsize))
        mix = jax.custom_vjp(self._mix)
        mix.defvjp(lambda *a: (self._mix(*a), a), self._backward)
        return mix(x, *ws)

    def weights(self):
        """The layer's leaves in the order `block` takes them."""
        return (self.qkv_proj, self.conv_weight, self.decay_down,
                self.decay_up, self.A_log, self.dt_bias, self.beta_proj,
                self.gate_down, self.gate_up, self.o_norm.weight, self.o_proj)

    def forward(self, x, ln_w):
        return apply_op(self.block, to_tensor_like(x), ln_w, *self.weights(),
                        name="kda_attention")


# -- a layer, the stack, the model ---------------------------------------------

class SolarOpen2DecoderLayer(Layer):
    def __init__(self, cfg: SolarOpen2Config, index: int):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if index in cfg.gqa_layers:
            self.self_attn = GatedAttention(cfg)
        else:
            self.linear_attn = KDAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                cfg.rms_norm_eps)
        self.mlp = dropless_moe_of(cfg)

    def forward(self, x):
        """Two taped operations: the mixer keeps only x and recomputes a
        group of heads at a time; the expert half keeps the mixer's
        output and recomputes itself whole."""
        mixer = self.self_attn if hasattr(self, "self_attn") \
            else self.linear_attn
        h = mixer(x, self.input_layernorm.weight)
        return moe_half(self.mlp, h, self.post_attention_layernorm.weight,
                        self.cfg.rms_norm_eps)


class SolarOpen2Model(DecoderStack):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__(cfg, SolarOpen2DecoderLayer)


class SolarOpen2ForCausalLM(CausalLM):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__(cfg, SolarOpen2Model)

    def loss(self, input_ids, labels):
        """Shifted next-token cross-entropy, the head and the loss a block
        of rows at a time: the last position of a sequence has no label."""
        nxt = shifted(labels)
        return blocked_loss(self.cfg, self.model(input_ids, final_norm=False),
                            self.model.norm.weight, self.lm_head, nxt)

    def moe_counters(self):
        """`pieces.moe_counters` of every layer."""
        return moe_counters(self.model.layers)
