"""dots3-note: a decoder whose attention is latent (MLA) in every layer,
in two parametrisations picked by `layer_types`, each layer followed by a
mixture of experts (a dense SwiGLU in the first `first_k_dense_replace`).

With x the layer's normed input at position t (one sequence):

  * both kinds (n heads, d_n no-rope, d_r rope, d_v value, ranks r_q, r_kv):
      c^Q = a_q RMSNorm(x W_DQ);   [q^N_h | q^R_h] = c^Q W_UQ,h;
      [c^KV | k^R] = x W_DKV;      c^KV <- a_kv RMSNorm(c^KV);
      q^R, k^R <- RoPE_theta (k^R one row for all heads);
      [k^N_h | v_h] = c^KV W_UKV,h;
      a_{t,s,h} = (q^N.k^N + q^R.k^R) / sqrt(d_n + d_r);
      o_{t,h} = sum_{s in S_t} softmax_{S_t}(a_{t,.,h}) v_{s,h};
      out_t = [sigmoid(x_t W_G)_h o_{t,h}]_h W_O      (a gate a head)
    a_q = sqrt(hidden / r_q), a_kv = sqrt(hidden / r_kv) where
    `mla_rescale`. ONE body (`LatentAttention`) serves every kind: sizes,
    rotary base and S_t differ, the code does not.
  * "sliding_attention": S_t = the `sliding_window_size` positions up to
    and with t (`kernels/flash_attention.py`: splash under a `LocalMask`,
    keys [k^N | k^R] 256 wide, values 128).
  * "causal_attention" (`models/glm4_moe_lite.py` builds its layers from
    this body; no dots3-note layer is of this kind): S_t = every s <= t, no
    gate (out_t = [o_{t,h}]_h W_O) and no rescale; splash under a
    `CausalMask`, keys [k^N | k^R] and values as wide as the config says.
  * "full_attention": S_t = the `index_topk` keys s <= t of largest index
    score (all while t < index_topk), I_{t,s} = sum_j w_{t,j}
    relu(q^I_{t,j} . k^I_s), q^I = c^Q W_IQ (`index_n_heads` of
    `index_head_dim`), k^I = LayerNorm(x W_IK), rotary on the first
    d_r dims of both, w = x W_IW / sqrt(heads * dim)
    (`kernels/sparse_select_attention.py`). The selection is hard: the
    language-model loss reaches the indexer through nothing. The indexer
    learns from its own loss, L_I = mean_t KL(p_t || softmax_{S_t} I_t)
    with p the heads' mean probability, x, c^Q and p under
    `stop_gradient`: `loss` returns L_LM + the full layers' L_I.
  * expert layers: `nn.DroplessMoE` told which experts it holds, sigmoid
    scores over all of them, the top-k of score + `e_score_correction_bias`
    (a buffer), weights the scores' own; one shared expert.

Memory decides the structure, as in `models/solar_open2.py`: a mixer is
ONE taped operation; the latents (a few hundred columns a token) are made
once, the heads go a group at a time (`head_group`) through their
up-projections, the core, the gate and their rows of W_O, and a group is
recomputed in the backward but for the attention kernel's out and
log-sum-exp, which the armed remat policy keeps. The index scores live
only through the forward of their layer; the backward reads the mask
(int8) and the gradient of L_I with respect to the scores (bfloat16).
The indexer's own products (projections and scores) are float32 at full
precision: a rounding of a score selects another key.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..autograd.tape import apply_op
from ..framework import core
from ..kernels.rope import softmax_scale
from ..kernels.rope import tables as _rope_tables
from ..nn import initializer as I
from ..nn.layer.layers import Layer
from ..observability.scopes import scope
from ..ops._helpers import to_tensor_like
from ..tensor import Tensor
from .pieces import (PLAIN, CausalLM, DecoderStack, RMSNorm, SwiGLUHalf,
                     blocked_loss, dropless_moe_of, group_of, moe_counters,
                     moe_half, param, rms, shifted)

__all__ = ["Dots3NoteConfig", "Dots3NoteModel", "Dots3NoteForCausalLM",
           "dots3_note_tiny"]

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"
CAUSAL = "causal_attention"      # dense causal, ungated: no dots3-note layer


@dataclass
class Dots3NoteConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    num_hidden_layers: int = 46
    layer_types: Optional[Tuple[str, ...]] = None   # None: full, then
    first_k_dense_replace: int = 1                  # (full, 3 sliding)*
    intermediate_size: int = 13824
    # full layers
    num_attention_heads: int = 128
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_loss_weight: float = 1.0
    # sliding layers
    swa_num_attention_heads: int = 64
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    mla_rescale: bool = True
    # expert layers
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 256      # the router's outputs
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    # expert parallelism: the experts [expert_offset, + experts_held) of
    # every layer live here (None: all of them)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_rows: Optional[int] = None
    head_group: int = 16
    loss_block_rows: int = 2048
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if i == 0 or i % 4 == 1 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        bad = set(self.layer_types) - {FULL, SLIDING}
        if bad or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"layer_types: {self.layer_types}")

    def attention(self, kind):
        """(heads, d_n, d_r, d_v, r_q, r_kv, theta) of a layer kind."""
        if kind == FULL:
            return (self.num_attention_heads, self.qk_nope_head_dim,
                    self.qk_rope_head_dim, self.v_head_dim, self.q_lora_rank,
                    self.kv_lora_rank, float(self.rope_theta))
        return (self.swa_num_attention_heads, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_q_lora_rank, self.swa_kv_lora_rank,
                float(self.swa_rope_theta))


def dots3_note_tiny(**kw):
    """Every mechanism at widths a CPU test can afford: the dense layer 0,
    then one period (full, sliding, sliding, sliding)."""
    base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=5,
                intermediate_size=48, num_attention_heads=4,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                q_lora_rank=16, kv_lora_rank=8, index_n_heads=2,
                index_head_dim=8, index_topk=16, swa_num_attention_heads=2,
                swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
                swa_v_head_dim=8, swa_q_lora_rank=16, swa_kv_lora_rank=16,
                sliding_window_size=9, moe_intermediate_size=16,
                n_routed_experts=8, num_experts_per_tok=2, head_group=2,
                loss_block_rows=8, dtype="float32")
    base.update(kw)
    return Dots3NoteConfig(**base)


def _rope(x, theta):
    """Rotate-half rotary (the pairing of `kernels/rope.py`) on x
    [S, ..., d] at positions 0..S-1, float32 inside."""
    from ..kernels.rope import _rotate_half
    cos, sin = _rope_tables(x.shape[0], x.shape[-1], theta)
    lead = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    xf = x.astype(_F32)
    return (xf * cos.reshape(lead)
            + _rotate_half(xf) * sin.reshape(lead)).astype(x.dtype)


def _latent_norm(a, w, eps, mult):
    from ..kernels import rms_norm as krn
    y = krn.rms_norm(a, w, eps)
    return y if mult == 1.0 else (y.astype(_F32) * mult).astype(a.dtype)


def _windowed(q, k, v, window, scale):
    """Dense causal attention inside a window (None: every causal key):
    q, k [S, h, d], v [S, h, dv] (the route for shapes no kernel takes)."""
    S = q.shape[0]
    s = jnp.einsum("thd,shd->hts", q.astype(_F32), k.astype(_F32)) * scale
    t, c = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = c <= t if window is None else (c <= t) & (t - c < window)
    s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1),
                      v.astype(_F32)).astype(v.dtype)


# -- latent attention ----------------------------------------------------------

class Indexer(Layer):
    """The selection's scorer: q from the query latent, k and the heads'
    weights from the layer input."""

    def __init__(self, cfg: Dots3NoteConfig):
        super().__init__()
        J, D = cfg.index_n_heads, cfg.index_head_dim
        dt = cfg.dtype
        self.wq_b = param(self, (cfg.q_lora_rank, J * D), P(None, "mp"),
                          dtype=dt)
        self.wk = param(self, (cfg.hidden_size, D), P(None, None), dtype=dt)
        self.k_norm_weight = param(self, (D,), P(None),
                                   init=I.Constant(1.0), dtype="float32")
        self.k_norm_bias = param(self, (D,), P(None), init=I.Constant(0.0),
                                 dtype="float32")
        self.weights_proj = param(self, (cfg.hidden_size, J),
                                  P(None, None), dtype=dt)

    def weights(self):
        return [self.wq_b, self.wk, self.k_norm_weight, self.k_norm_bias,
                self.weights_proj]


class LatentAttention(Layer):
    """MLA(RMSNorm(.)) of one layer kind as a half-layer on the residual
    path its caller hands it (`pieces.PLAIN`: x + MLA(RMSNorm(x))); see the
    module docstring."""

    def __init__(self, cfg: Dots3NoteConfig, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        h, dt = cfg.hidden_size, cfg.dtype
        n, dn, dr, dv, rq, rkv, _ = cfg.attention(kind)
        self.q_a_proj = param(self, (h, rq), P(None, None), dtype=dt)
        self.q_a_layernorm = RMSNorm(rq, cfg.rms_norm_eps)
        self.q_b_proj = param(self, (rq, n * (dn + dr)), P(None, "mp"),
                              dtype=dt)
        self.kv_a_proj = param(self, (h, rkv + dr), P(None, None), dtype=dt)
        self.kv_a_layernorm = RMSNorm(rkv, cfg.rms_norm_eps)
        self.kv_b_proj = param(self, (rkv, n * (dn + dv)), P(None, "mp"),
                               dtype=dt)
        if kind != CAUSAL:
            self.gate_proj = param(self, (h, n), P(None, "mp"), dtype=dt)
        self.o_proj = param(self, (n * dv, h), P("mp", None), dtype=dt)
        if kind == FULL:
            self.indexer = Indexer(cfg)
            self.register_buffer("attended_pairs",
                                 Tensor(jnp.zeros((), jnp.int32)))

    def _groups(self):
        n, hg = self.cfg.attention(self.kind)[0], self.cfg.head_group
        return n // hg if n % hg == 0 else 1

    def _mults(self):
        cfg = self.cfg
        _, _, _, _, rq, rkv, _ = cfg.attention(self.kind)
        if not cfg.mla_rescale:
            return 1.0, 1.0
        return (math.sqrt(cfg.hidden_size / rq),
                math.sqrt(cfg.hidden_size / rkv))

    # -- the parts, on one sequence: x [S, H] ---------------------------------

    def _latents(self, x, ln_w, wdq, qn_w, wdkv, kvn_w, wg):
        """(c^Q [S, r_q], c^KV [S, r_kv], rotated k^R [S, d_r], the heads'
        gates [S, n] float32, or None for a kind without gates)."""
        cfg = self.cfg
        _, _, _, _, _, rkv, theta = cfg.attention(self.kind)
        a_q, a_kv = self._mults()
        xn = rms(x, ln_w, cfg.rms_norm_eps)
        with scope("attn/qkv"):
            cq = _latent_norm(xn @ wdq, qn_w, cfg.rms_norm_eps, a_q)
            down = xn @ wdkv
            ckv = _latent_norm(down[:, :rkv], kvn_w, cfg.rms_norm_eps, a_kv)
        with scope("attn/rope"):
            kr = _rope(down[:, rkv:], theta)
        if wg is None:
            return cq, ckv, kr, None
        with scope("attn/gate"):
            gate = jax.nn.sigmoid((xn @ wg).astype(_F32))
        return cq, ckv, kr, gate

    def _heads(self, g, cq, ckv, wuq, wukv):
        """Group g's (q^N, rotated q^R, k^N, v), each [hg, S, d]."""
        n, dn, dr, dv, _, _, theta = self.cfg.attention(self.kind)
        G = self._groups()
        hg = n // G
        S = cq.shape[0]
        with scope("attn/qkv"):
            q = (cq @ group_of(wuq, 1, G, g)).reshape(S, hg, dn + dr)
            kv = (ckv @ group_of(wukv, 1, G, g)).reshape(S, hg, dn + dv)
        with scope("attn/rope"):
            qr = _rope(q[..., dn:], theta)
        sw = lambda a: jnp.swapaxes(a, 0, 1)
        return sw(q[..., :dn]), sw(qr), sw(kv[..., :dn]), sw(kv[..., dn:])

    def _group(self, g, cq, ckv, kr, gate, mask, wuq, wukv, wo):
        """Heads of group g: (their part of the output projection [S, H]
        float32, their log-sum-exp [hg, S] or None)."""
        cfg = self.cfg
        n, dn, dr, dv, _, _, theta = cfg.attention(self.kind)
        G = self._groups()
        hg = n // G
        S = cq.shape[0]
        scale = softmax_scale(theta, dn + dr)
        qn, qr, kn, v = self._heads(g, cq, ckv, wuq, wukv)
        lse = None
        if self.kind == FULL:
            from ..kernels import sparse_select_attention as dsa
            with scope("attn/core/selected"):
                o, lse = dsa.selected_attention(qn, qr, kn, kr, v, mask,
                                                scale)
                o = jnp.swapaxes(o, 0, 1)                  # [S, hg, dv]
        else:
            from ..kernels import flash_attention as fa
            W = cfg.sliding_window_size if self.kind == SLIDING else None
            with scope("attn/core/causal" if W is None
                       else "attn/core/window"):
                q = jnp.swapaxes(jnp.concatenate([qn, qr], -1), 0, 1)
                k = jnp.swapaxes(jnp.concatenate(
                    [kn, jnp.broadcast_to(kr[None], (hg,) + kr.shape)], -1),
                    0, 1)
                vv = jnp.swapaxes(v, 0, 1)
                # keys 128 + 64 wide: zeros up to the next 128 lanes add
                # nothing to q.k and cost the matrix unit nothing (it
                # contracts 128 at a time), and the kernel takes them
                pad = -q.shape[-1] % 128
                if pad and q.shape[-1] > 128 and fa.supported(
                        (1,) + q.shape[:-1] + (q.shape[-1] + pad,),
                        (1,) + k.shape[:-1] + (k.shape[-1] + pad,), True,
                        v_dim=dv, window=W):
                    q, k = (jnp.pad(a, ((0, 0), (0, 0), (0, pad)))
                            for a in (q, k))
                if fa.supported((1,) + q.shape, (1,) + k.shape, True,
                                v_dim=dv, window=W):
                    o = fa.flash_attention_bshd(
                        q[None], k[None], vv[None], causal=True, scale=scale,
                        window=W)[0]
                else:
                    o = _windowed(q, k, vv, W, scale)
        if gate is not None:
            with scope("attn/gate"):
                gg = jax.lax.dynamic_slice_in_dim(gate, g * hg, hg, axis=1)
                o = (o.astype(_F32) * gg[:, :, None]).astype(cq.dtype)
        with scope("attn/out"):
            wo_g = jax.lax.dynamic_index_in_dim(
                wo.reshape(G, hg * dv, -1), g, 0, keepdims=False)
            out = jnp.matmul(o.reshape(S, hg * dv), wo_g,
                             preferred_element_type=_F32)
        return out, lse

    def _mix(self, lat, mask, wuq, wukv, wo):
        """sum over the groups of `_group`, and the groups' log-sum-exp
        stacked ([G, hg, S]; None for a window or dense-causal layer)."""
        cq = lat[0]
        run = jax.checkpoint(self._group,
                             policy=core.current_remat_policy())

        def body(acc, g):
            out, lse = run(g, *lat, mask, wuq, wukv, wo)
            return acc + out, lse

        acc, lse = jax.lax.scan(
            body, jnp.zeros((cq.shape[0], wo.shape[1]), _F32),
            jnp.arange(self._groups()))
        return acc.astype(cq.dtype), lse

    def _index_inputs(self, x, ln_w, wdq, qn_w, wiq, wik, ik_w, ik_b, wiw):
        """(q^I [S, J, D], k^I [S, D], w [S, J]) in float32 at full
        precision, from the layer input and the query latent made again
        in float32 (both constants to the indexer's loss)."""
        cfg = self.cfg
        J, D, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
        eps, theta = cfg.rms_norm_eps, float(cfg.rope_theta)
        a_q = self._mults()[0]
        mm = lambda a, w: jnp.matmul(a, w.astype(_F32), precision=_HI)
        rms = lambda a, w: a * jax.lax.rsqrt(
            jnp.mean(a * a, -1, keepdims=True) + eps) * w
        xn = rms(x.astype(_F32), ln_w)
        cq = rms(mm(xn, wdq), qn_w) * a_q
        xn, cq = jax.lax.stop_gradient((xn, cq))
        qi = mm(cq, wiq).reshape(-1, J, D)
        k = mm(xn, wik)
        mu = jnp.mean(k, -1, keepdims=True)
        var = jnp.mean(jnp.square(k - mu), -1, keepdims=True)
        ki = (k - mu) * jax.lax.rsqrt(var + eps) * ik_w + ik_b
        qi = jnp.concatenate([_rope(qi[..., :dr], theta), qi[..., dr:]], -1)
        ki = jnp.concatenate([_rope(ki[..., :dr], theta), ki[..., dr:]], -1)
        w = mm(xn, wiw) * (1.0 / math.sqrt(J * D))
        return qi, ki, w

    def _target(self, lat, lse, mask, wuq, wukv):
        """The heads' summed probabilities on the selected pairs [S, S]
        float32, a group at a time (forward only: a constant)."""
        from ..kernels import sparse_select_attention as dsa
        cfg = self.cfg
        _, dn, dr, _, _, _, theta = cfg.attention(self.kind)
        cq, ckv, kr, _ = lat
        scale = softmax_scale(theta, dn + dr)

        def add(acc, g, lse_g):
            qn, qr, kn, _ = self._heads(g, cq, ckv, wuq, wukv)
            return dsa.head_prob_sum(qn, qr, kn, kr, lse_g, mask, scale, acc)

        # the first group starts the sum: no [S, S] array of zeros beside it
        first = add(None, jnp.int32(0), lse[0])
        if self._groups() == 1:
            return first
        acc, _ = jax.lax.scan(
            lambda acc, a: (add(acc, *a), None), first,
            (jnp.arange(1, self._groups()), lse[1:]))
        return acc

    def _sequence(self, x, ln_w, wdq, qn_w, wuq, wdkv, kvn_w, wukv, *rest):
        """One sequence x [S, H] -> (the mixer's output, no add; L_I;
        pairs attended); `rest` = (W_G but for a dense-causal layer, W_O,
        the indexer's leaves of a full layer), as `forward` lists them."""
        sg = jax.lax.stop_gradient
        wg, wo, *index_ws = (None,) + rest if self.kind == CAUSAL else rest
        lat = jax.checkpoint(self._latents)(x, ln_w, wdq, qn_w, wdkv, kvn_w,
                                            wg)
        if self.kind != FULL:
            mixed, _ = self._mix(lat, None, wuq, wukv, wo)
            return mixed, jnp.zeros((), _F32), jnp.zeros((), jnp.int32)
        from ..kernels import sparse_select_attention as dsa
        with scope("attn/index"):
            qi, ki, w = jax.checkpoint(self._index_inputs)(
                sg(x), sg(ln_w), sg(wdq), sg(qn_w), *index_ws)
            scores = dsa.index_scores(sg(qi), sg(ki), sg(w))
        with scope("attn/select"):
            mask, lse_i = dsa.select_top_k(scores, self.cfg.index_topk)
            pairs = jnp.sum(mask, dtype=jnp.int32)
        mixed, lse = self._mix(lat, mask, wuq, wukv, wo)
        with scope("attn/index"):
            psum = self._target(sg(lat), sg(lse), mask, sg(wuq), sg(wukv))
            li = dsa.indexer_loss(qi, ki, w, scores, mask, lse_i, psum,
                                  self.cfg.attention(FULL)[0])
        return mixed, li, pairs

    def block(self, x, *ws, path=PLAIN):
        """The path's half around `_sequence`, which takes one sequence: on
        the plain path x [B, S, H] -> (x + mixer [B, S, H], mean L_I [],
        pairs [], then what the path appends); `ws` the path's leaves
        first."""
        k = len(path.leaves())
        return path.by_sequence(
            lambda u: self._sequence(u, *ws[k:]), x, ws[:k],
            gather=lambda li, pairs: (jnp.mean(li), jnp.sum(pairs)),
            under="attn/out")

    def forward(self, x, ln_w, path=PLAIN):
        ws = [ln_w, self.q_a_proj, self.q_a_layernorm.weight, self.q_b_proj,
              self.kv_a_proj, self.kv_a_layernorm.weight, self.kv_b_proj]
        if self.kind != CAUSAL:
            ws.append(self.gate_proj)
        ws.append(self.o_proj)
        if self.kind == FULL:
            ws += self.indexer.weights()
        y, li, pairs, *extra = apply_op(
            functools.partial(self.block, path=path), to_tensor_like(x),
            *path.leaves(), *ws, n_outputs=3 + path.extra,
            name="latent_attention")
        path.record(*(e.data for e in extra))
        if self.kind == FULL:
            self.attended_pairs.data = pairs.data
            return y, li
        return y, None


# -- a layer, the stack, the model ---------------------------------------------

class Dots3NoteMLP(SwiGLUHalf):
    """h + SwiGLU(RMSNorm(h)) of a leading dense layer."""

    def __init__(self, cfg):
        super().__init__(cfg, "dots3_note_mlp")


class Dots3NoteDecoderLayer(Layer):
    def __init__(self, cfg: Dots3NoteConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LatentAttention(cfg, cfg.layer_types[index])
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                cfg.rms_norm_eps)
        if index < cfg.first_k_dense_replace:
            self.mlp = Dots3NoteMLP(cfg)
        else:
            self.mlp = dropless_moe_of(cfg, selection_bias=True)

    def forward(self, x):
        """(y, L_I or None). Two taped operations: the mixer keeps x, its
        latents and what the attention kernels name; the second half keeps
        the mixer's output and recomputes itself whole."""
        h, li = self.self_attn(x, self.input_layernorm.weight)
        ln_w = self.post_attention_layernorm.weight
        if isinstance(self.mlp, Dots3NoteMLP):
            return self.mlp(h, ln_w), li
        return moe_half(self.mlp, h, ln_w, self.cfg.rms_norm_eps), li


class Dots3NoteModel(DecoderStack):
    def __init__(self, cfg: Dots3NoteConfig):
        super().__init__(cfg, Dots3NoteDecoderLayer)

    def forward(self, input_ids, final_norm=True, with_aux=False):
        aux = []
        x = super().forward(input_ids, final_norm, aux)
        return (x, aux) if with_aux else x


class Dots3NoteForCausalLM(CausalLM):
    def __init__(self, cfg: Dots3NoteConfig):
        super().__init__(cfg, Dots3NoteModel)

    def losses(self, input_ids, labels):
        """(L_LM, [L_I of each full layer]): the shifted next-token
        cross-entropy, head and loss a block of rows at a time."""
        nxt = shifted(labels)
        x, aux = self.model(input_ids, final_norm=False, with_aux=True)
        return blocked_loss(self.cfg, x, self.model.norm.weight,
                            self.lm_head, nxt), aux

    def loss(self, input_ids, labels):
        """L_LM + `indexer_loss_weight` * the full layers' L_I."""
        lm, aux = self.losses(input_ids, labels)
        w = self.cfg.indexer_loss_weight

        def total(lm_, *li):
            with scope("loss"):
                return lm_ + w * sum(li).astype(lm_.dtype) if li else lm_

        return apply_op(total, lm, *aux, name="total_loss")

    def moe_counters(self):
        """`pieces.moe_counters` of the expert layers, and
        "attended_pairs": [full layers]."""
        layers = self.model.layers
        return moe_counters(layers, attended_pairs=[
            lyr.self_attn.attended_pairs for lyr in layers
            if lyr.self_attn.kind == FULL])
