"""The plain reference of the dots3-note language model: a pre-norm decoder
whose attention is latent (MLA) in every layer, in two parametrisations
picked by `layer_types`, and whose feed-forward is a sigmoid-routed mixture
of experts with one shared expert (a dense SwiGLU in the first
`first_k_dense_replace` layers). Written from the equations in `jax.numpy`
float32 at matmul precision "highest": no kernel, nothing imported from
the program (the control's rounding and the optimizer are
`chipbench/reference.py`'s). The CPU tests hold
`paddle_tpu/models/dots3_note.py` to this file, and `train_steps` below
decides the benchmark cell's `correct`.

Equations (x the layer input, one sequence, eps the config's):
  block   h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
  MLA     (n heads, d_n no-rope, d_r rope, d_v value, ranks r_q, r_kv, theta)
          c^Q = a_q RMSNorm(x W_DQ);  [q^N_h | q^R_h] = c^Q W_UQ,h;
          [c^KV | k^R] = x W_DKV;  c^KV <- a_kv RMSNorm(c^KV);
          q^R, k^R <- RoPE_theta (rotate-half pairing; k^R one for all heads);
          [k^N_h | v_h] = c^KV W_UKV,h;
          a_tsh = (q^N.k^N + q^R.k^R) / sqrt(d_n + d_r);
          o_th = sum_{s in S_t} softmax_{S_t}(a_t.h) v_sh;
          out_t = [sigmoid(x_t W_G)_h o_th]_h W_O
          a_q = sqrt(hidden / r_q), a_kv = sqrt(hidden / r_kv) (`rescale`)
  sliding S_t = {s : 0 <= t - s < window}
  full    q^I_tj = c^Q_t W_IQ,j; k^I_s = LayerNorm(x_s W_IK); RoPE_theta on
          the first d_r dims of both; w_t = x_t W_IW / sqrt(J D);
          I_ts = sum_j w_tj relu(q^I_tj . k^I_s);
          S_t = the top_k keys s <= t of largest I_ts (all while t < top_k);
          L_I = mean_t KL(p_t || softmax_{S_t} I_t), p_ts the heads' mean
          probability; x, c^Q and p constants to it (stop_gradient), the
          selection hard: L_LM reaches no indexer weight, L_I nothing else
  MoE     s = sigmoid(x Wr) over ALL experts; top-k by s + b (b a buffer);
          w = s_top / sum(s_top) * routed_scaling_factor;
          y = Shared(x) + sum_k w_k E_k(x), E and Shared SwiGLU.
`held = (e0, n)`: the experts [e0, e0 + n) live here, the router keeps
every output and its top-k, and a pair routed to an absent expert adds
nothing. held = (0, n_routed) is the uncut layer.
`loss` = L_LM + li_weight * sum over full layers of L_I.

Departures, each because plain f32 at the benchmark's sizes would not fit
one chip, none changing a value: attention runs one group of heads and one
block of query rows at a time (a sliding layer's block reads only the
keys of its band), the index scores a block of rows at a time and an
index head at a time (`jax.lax.top_k` of a row's causal scores: of equal
scores the lower position first), the heads' probabilities are made again
from their
log-sum-exp for the indexer's target, the head and loss run a block of
rows at a time, backward passes recompute inside blocks, and an expert
multiplies only the (at most `cap`) rows routed to it.

State-dict layout (matrices [in, out]): `q_b_proj` holds a head's
[q^N | q^R] columns side by side, head after head; `kv_b_proj` a head's
[k^N | v]; `kv_a_proj` [c^KV | k^R]; `*gate_up*` gate | up columns; expert
stacks are [n_held, ...].

`mode` computes every weight matmul but the router's and the indexer's in
a lower precision (the control of `correct`): "fp8" (e4m3, per-row /
per-column scales), "int8" or "bf16"; the gradient passes straight through.
"""
from __future__ import annotations

import concurrent.futures
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import (_adamw, _diff_norm, _embed, _embed_grad,
                                 _fake_quant)

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
NEG = -1e30
FULL, SLIDING = "full_attention", "sliding_attention"


class Kind(NamedTuple):
    n: int
    dn: int
    dr: int
    dv: int
    rq: int
    rkv: int
    theta: float


class Arch(NamedTuple):
    hidden: int
    eps: float
    layer_types: tuple
    first_dense: int
    full: Kind
    swa: Kind
    window: int
    ji: int            # index heads
    di: int            # index head width
    top_idx: int
    li_weight: float
    rescale: bool
    m: int             # expert width
    n_routed: int
    top_k: int
    norm_topk: bool
    scaling: float


def arch(cfg):
    """The static sizes the equations need, from a configuration file."""
    first = cfg.get("layer_offset", 0)      # a pipeline stage's first layer
    L = first + cfg["num_hidden_layers"]
    return Arch(
        hidden=cfg["hidden_size"], eps=float(cfg["rms_norm_eps"]),
        layer_types=tuple(cfg["layer_types"][first:L]),
        first_dense=cfg["first_k_dense_replace"],
        full=Kind(cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                  cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                  cfg["q_lora_rank"], cfg["kv_lora_rank"],
                  float(cfg["rope_theta"])),
        swa=Kind(cfg["swa_num_attention_heads"], cfg["swa_qk_nope_head_dim"],
                 cfg["swa_qk_rope_head_dim"], cfg["swa_v_head_dim"],
                 cfg["swa_q_lora_rank"], cfg["swa_kv_lora_rank"],
                 float(cfg["swa_rope_theta"])),
        window=cfg["sliding_window_size"], ji=cfg["index_n_heads"],
        di=cfg["index_head_dim"], top_idx=cfg["index_topk"],
        li_weight=float(cfg.get("indexer_loss_weight", 1.0)),
        rescale=bool(cfg["apply_mla_qkv_lora_rescale"]),
        m=cfg["moe_intermediate_size"],
        n_routed=cfg.get("reduced_from", {}).get("n_routed_experts",
                                                 cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]))


def held_of(cfg):
    """(first expert, experts) this configuration holds of each layer."""
    return cfg.get("expert_offset", 0), cfg["n_routed_experts"]


def vocab_of(cfg):
    """Rows of the vocabulary this configuration holds."""
    return cfg.get("vocab_rows", cfg["vocab_size"])


def layer_kind(a, i):
    return a.layer_types[i]


def _mm(a, w, mode=None):
    return jnp.matmul(_fake_quant(a, mode, -1), _fake_quant(w, mode, 0),
                      precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, w_gate_up, w_down, mode):
    gu = _mm(x, w_gate_up, mode)
    m = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :m]) * gu[..., m:], w_down, mode)


def _rope_tables(T, d, theta):
    """cos and sin of t * theta^(-2i/d), [T, d/2] float32, made on the
    host in float64: at 16384 positions an angle is thousands of radians,
    and a chip's float32 power, sine and cosine are not exact there (a
    relative 1e-5 of a frequency is 0.09 rad at the far end)."""
    freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(T, dtype=np.float64)[:, None] * freq[None]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rope(x, theta):
    """Rotary on x [T, ..., d] at positions 0..T-1: dim i pairs with dim
    i + d/2, angle t * theta^(-2i/d)."""
    T, d = x.shape[0], x.shape[-1]
    shape = (T,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = (t.reshape(shape) for t in _rope_tables(T, d, theta))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block_of(T, block):
    return block if T % block == 0 else T


# -- latent attention ----------------------------------------------------------

def _latents(w, xn, a, k, mode):
    """(c^Q [T, r_q], c^KV [T, r_kv], rotated k^R [T, d_r], gates [T, n])."""
    a_q = math.sqrt(a.hidden / k.rq) if a.rescale else 1.0
    a_kv = math.sqrt(a.hidden / k.rkv) if a.rescale else 1.0
    cq = a_q * _rms(_mm(xn, w["q_a_proj"], mode), w["q_a_layernorm.weight"],
                    a.eps)
    down = _mm(xn, w["kv_a_proj"], mode)
    ckv = a_kv * _rms(down[:, :k.rkv], w["kv_a_layernorm.weight"], a.eps)
    kr = _rope(down[:, k.rkv:], k.theta)
    gate = jax.nn.sigmoid(_mm(xn, w["gate_proj"], mode))
    return cq, ckv, kr, gate


def _head_groups(k):
    """Groups the heads are handled in: 8 heads each where they divide."""
    return k.n // 8 if k.n % 8 == 0 else 1


def _grouped(w, gate, k):
    """The up-projections, the gates and W_O by group of heads."""
    G = _head_groups(k)
    hg = k.n // G
    return {
        "uq": w["q_b_proj"].reshape(k.rq, G, hg * (k.dn + k.dr)).transpose(
            1, 0, 2),
        "ukv": w["kv_b_proj"].reshape(k.rkv, G, hg * (k.dn + k.dv)
                                      ).transpose(1, 0, 2),
        "o": w["o_proj"].reshape(G, hg * k.dv, -1),
        "gate": gate.reshape(-1, G, hg).transpose(1, 0, 2),
    }


def _group_qkv(cq, ckv, kr, wg, k, mode):
    """A group's q [T, hg, d_n + d_r] (rope applied), k likewise (k^R
    repeated), v [T, hg, d_v]."""
    q = _mm(cq, wg["uq"], mode).reshape(cq.shape[0], -1, k.dn + k.dr)
    q = jnp.concatenate([q[..., :k.dn], _rope(q[..., k.dn:], k.theta)], -1)
    kv = _mm(ckv, wg["ukv"], mode).reshape(cq.shape[0], -1, k.dn + k.dv)
    hg = kv.shape[1]
    key = jnp.concatenate(
        [kv[..., :k.dn], jnp.broadcast_to(kr[:, None], (kr.shape[0], hg,
                                                         k.dr))], -1)
    return q, key, kv[..., k.dn:]


def _scores(qs, key, allowed):
    d = qs.shape[-1]
    s = jnp.einsum("thd,shd->hts", qs, key, precision=HI) / math.sqrt(d)
    return jnp.where(allowed[None], s, NEG)


def _attend(q, key, v, allow, window=None, block=256):
    """(o [T, hg, dv], lse [hg, T]) a block of query rows at a time.
    `allow(i, rows)` is the [block, keys] mask of rows block i; with a
    `window` a block reads only the keys of its band."""
    T = q.shape[0]
    block = _block_of(T, block)
    if window is not None:
        pad = window - 1
        key = jnp.pad(key, ((pad, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        t = i * block + jnp.arange(block)
        if window is None:
            ks, vs, ok = key, v, allow(i, t)
        else:
            n = block + window - 1
            ks = jax.lax.dynamic_slice_in_dim(key, i * block, n, 0)
            vs = jax.lax.dynamic_slice_in_dim(v, i * block, n, 0)
            c = i * block - (window - 1) + jnp.arange(n)
            ok = ((c[None] >= 0) & (c[None] <= t[:, None])
                  & (t[:, None] - c[None] < window))
        s = _scores(qs, ks, ok)
        lse = jax.nn.logsumexp(s, axis=-1)                      # [hg, blk]
        p = jnp.exp(s - lse[..., None])
        return jnp.einsum("hts,shd->thd", p, vs, precision=HI), lse

    o, lse = jax.lax.map(jax.checkpoint(rows), jnp.arange(T // block))
    return (o.reshape((T,) + o.shape[2:]),
            jnp.moveaxis(lse, 0, 1).reshape(lse.shape[1], T))


def _mixer(w, lat, a, k, mode, mask=None):
    """(attention output [T, H], the groups' log-sum-exp [G, hg, T]) from
    the latents, one group of heads at a time; `mask` [T, T] the
    selection, or the window."""
    cq, ckv, kr, gate = lat

    def allow(i, t):
        return jax.lax.dynamic_slice_in_dim(mask, t[0], t.shape[0], 0)

    def group(lat, wg):
        q, key, v = _group_qkv(*lat, wg, k, mode)
        o, lse = _attend(q, key, v, allow,
                         None if mask is not None else a.window)
        o = o * wg["gate"][:, :, None]
        return _mm(o.reshape(o.shape[0], -1), wg["o"], mode), lse

    def body(acc, wg):
        out, lse = jax.checkpoint(group)((cq, ckv, kr), wg)
        return acc + out, lse

    out, lse = jax.lax.scan(
        body, jnp.zeros((cq.shape[0], w["o_proj"].shape[1]), F32),
        _grouped(w, gate, k))
    return out, lse


# -- the learned selection -----------------------------------------------------

def indexer_inputs(w, xn, cq, a):
    """(q^I [T, J, D], k^I [T, D], w [T, J]); xn and c^Q are constants."""
    xn, cq = jax.lax.stop_gradient((xn, cq))
    dr = a.full.dr
    qi = _mm(cq, w["indexer.wq_b"]).reshape(-1, a.ji, a.di)
    k = _mm(xn, w["indexer.wk"])
    mu = jnp.mean(k, -1, keepdims=True)
    var = jnp.mean((k - mu) ** 2, -1, keepdims=True)
    ki = ((k - mu) * jax.lax.rsqrt(var + a.eps) * w["indexer.k_norm_weight"]
          + w["indexer.k_norm_bias"])
    qi = jnp.concatenate([_rope(qi[..., :dr], a.full.theta), qi[..., dr:]],
                         -1)
    ki = jnp.concatenate([_rope(ki[..., :dr], a.full.theta), ki[..., dr:]],
                         -1)
    wi = _mm(xn, w["indexer.weights_proj"]) / math.sqrt(a.ji * a.di)
    return qi, ki, wi


def index_rows(qi, ki, wi):
    """I of some rows: qi [R, J, D], ki [T, D], wi [R, J] -> [R, T], an
    index head at a time."""
    def head(acc, qw):
        q, wj = qw
        d = jnp.matmul(q, ki.T, precision=HI)
        return acc + wj[:, None] * jax.nn.relu(d), None

    acc, _ = jax.lax.scan(
        head, jnp.zeros((qi.shape[0], ki.shape[0]), F32),
        (jnp.swapaxes(qi, 0, 1), jnp.swapaxes(wi, 0, 1)))
    return acc


def selection(qi, ki, wi, top_k, block=256):
    """mask [T, T]: row t keeps its top_k causal keys by index score."""
    T = qi.shape[0]
    block = _block_of(T, block)
    kk = min(top_k, T)

    def rows(i):
        t = i * block + jnp.arange(block)
        sc = index_rows(jax.lax.dynamic_slice_in_dim(qi, i * block, block),
                        ki, jax.lax.dynamic_slice_in_dim(wi, i * block,
                                                         block))
        seen = jnp.arange(T)[None] <= t[:, None]
        idx = jax.lax.top_k(jnp.where(seen, sc, NEG), kk)[1]
        picked = jnp.zeros((block, T), bool).at[
            jnp.arange(block)[:, None], idx].set(True)
        return seen & picked

    return jax.lax.map(rows, jnp.arange(T // block)).reshape(T, T)


def head_probabilities(lat, lse, w, mask, a, mode, block=256):
    """sum over heads of softmax probability on the selected pairs [T, T],
    made again from the heads' log-sum-exp (a constant)."""
    k = a.full
    cq, ckv, kr, gate = lat
    T = cq.shape[0]
    block = _block_of(T, block)

    def group(acc, xs):
        wg, lse_g = xs
        q, key, _ = _group_qkv(cq, ckv, kr, wg, k, mode)

        def rows(i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
            ok = jax.lax.dynamic_slice_in_dim(mask, i * block, block, 0)
            s = _scores(qs, key, ok)
            ls = jax.lax.dynamic_slice_in_dim(lse_g, i * block, block, 1)
            return jnp.sum(jnp.where(ok[None], jnp.exp(s - ls[..., None]),
                                     0.0), axis=0)

        return acc + jax.lax.map(rows, jnp.arange(T // block)).reshape(
            T, T), None

    acc, _ = jax.lax.scan(group, jnp.zeros((T, T), F32),
                          (_grouped(w, gate, k), lse))
    return acc


def indexer_kl(qi, ki, wi, mask, p, block=256):
    """mean_t KL(p_t || softmax over the selected keys of I_t), a block of
    rows at a time, the scores made again in each."""
    T = qi.shape[0]
    block = _block_of(T, block)

    def rows(args):
        q, wj, ok, pt = args
        sc = jnp.where(ok, index_rows(q, ki, wj), NEG)
        logq = sc - jax.nn.logsumexp(sc, axis=-1, keepdims=True)
        safe = jnp.where(pt > 0, pt, 1.0)
        return jnp.sum(jnp.where(ok & (pt > 0),
                                 pt * (jnp.log(safe) - logq), 0.0))

    n = T // block
    parts = jax.lax.map(
        jax.checkpoint(rows),
        (qi.reshape((n, block) + qi.shape[1:]), wi.reshape(n, block, -1),
         mask.reshape(n, block, T), p.reshape(n, block, T)))
    return jnp.sum(parts) / T


def full_mixer(w, xn, a, mode):
    """(attention output, L_I, the selection mask) of a full layer."""
    k = a.full
    sg = jax.lax.stop_gradient
    lat = _latents(w, xn, a, k, mode)
    qi, ki, wi = indexer_inputs(w, xn, lat[0], a)
    mask = selection(sg(qi), sg(ki), sg(wi), a.top_idx)
    out, lse = _mixer(w, lat, a, k, mode, mask)
    psum = sg(head_probabilities(sg(lat), sg(lse), sg(w), mask, a, mode))
    return out, indexer_kl(qi, ki, wi, mask, psum / k.n), mask


# -- the feed-forward halves ---------------------------------------------------

def route(xn, w_router, bias, a):
    """(expert ids [T, k], weights [T, k]) over all the router's outputs:
    the top-k of score + bias, weighted by the scores alone."""
    s = jax.nn.sigmoid(jnp.matmul(xn, w_router, precision=HI))
    top_i = jax.lax.top_k(s + jax.lax.stop_gradient(bias), a.top_k)[1]
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if a.norm_topk:
        top_s = top_s / jnp.sum(top_s, -1, keepdims=True)
    return top_i, top_s * a.scaling


def _moe(w, xn, a, held, mode, cap):
    """Shared expert + the held experts' part. Returns (y, rows sent to
    each held expert [n])."""
    T = xn.shape[0]
    e0, n = held
    cap = T if cap is None else min(cap, T)
    top_i, top_w = route(xn, w["router"], w["e_score_correction_bias"], a)

    def expert(xe, we):
        return _swiglu(xe, we["gu"], we["down"], mode)

    def body(y, ew):
        e, we = ew
        hit = top_i == e
        mine = jnp.any(hit, -1)
        wt = jnp.sum(jnp.where(hit, top_w, 0.0), -1)
        rows = jnp.nonzero(mine, size=cap, fill_value=T)[0]
        xe = jnp.take(xn, rows, axis=0, mode="fill", fill_value=0.0)
        ye = jax.checkpoint(expert)(xe, we) * jnp.take(
            wt, rows, mode="fill", fill_value=0.0)[:, None]
        return y.at[rows].add(ye, mode="drop"), jnp.sum(mine)

    shared = _swiglu(xn, w["shared_gate_up"], w["shared_down"], mode)
    y, sent = jax.lax.scan(
        body, shared, (e0 + jnp.arange(n),
                       {"gu": w["experts_gate_up"],
                        "down": w["experts_down"]}))
    return y, sent


# -- a layer, the head, the whole ---------------------------------------------

_MLA = ("q_a_proj", "q_a_layernorm.weight", "q_b_proj", "kv_a_proj",
        "kv_a_layernorm.weight", "kv_b_proj", "gate_proj", "o_proj")
_INDEXER = ("indexer.wq_b", "indexer.wk", "indexer.k_norm_weight",
            "indexer.k_norm_bias", "indexer.weights_proj")
_MOE = ("router", "experts_gate_up", "experts_down", "shared_gate_up",
        "shared_down", "e_score_correction_bias")
_DENSE = ("gate_up_proj", "down_proj")
BUFFERS = ("e_score_correction_bias",)       # in the state, never trained


def layer_names(a, i):
    """{key the equations use: state-dict name} of layer i."""
    base = f"model.layers.{i}."
    leaves = _MLA + (_INDEXER if layer_kind(a, i) == FULL else ())
    names = {"ln1": base + "input_layernorm.weight",
             "ln2": base + "post_attention_layernorm.weight"}
    names.update({"mixer." + k: base + "self_attn." + k for k in leaves})
    names.update({"mlp." + k: base + "mlp." + k
                  for k in (_DENSE if i < a.first_dense else _MOE)})
    return names


def _part(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def mixer_half(w, x, kind, a, mode=None):
    """(h = x + Attn(RMSNorm(x)) on x [B, T, H] float32, mean L_I [],
    pairs attended [] (0 for a sliding layer))."""
    def one(xr):
        xn = _rms(xr, w["ln1"], a.eps)
        if kind == FULL:
            out, li, mask = full_mixer(_part(w, "mixer."), xn, a, mode)
            return xr + out, li, jnp.sum(mask)
        m = _part(w, "mixer.")
        out = _mixer(m, _latents(m, xn, a, a.swa, mode), a, a.swa, mode)[0]
        return xr + out, jnp.zeros((), F32), jnp.zeros((), jnp.int32)

    h, li, pairs = jax.lax.map(one, x)
    return h, jnp.mean(li), jnp.sum(pairs)


def expert_half(w, h, a, held, mode=None, cap=None):
    """(h + MoE(RMSNorm(h)), rows sent to each held expert [B, n])."""
    def one(hr):
        y, sent = _moe(_part(w, "mlp."), _rms(hr, w["ln2"], a.eps), a, held,
                       mode, cap)
        return hr + y, sent

    return jax.lax.map(one, h)


def dense_half(w, h, a, mode=None):
    """h + SwiGLU(RMSNorm(h)) of a leading dense layer."""
    m = _part(w, "mlp.")
    return h + _swiglu(_rms(h, w["ln2"], a.eps), m["gate_up_proj"],
                       m["down_proj"], mode)


def layer(w, x, i, a, held, mode=None, cap=None):
    """Layer i on x [B, T, H] (float32): (y, L_I, pairs attended, rows sent
    to each held expert [B, n] or None)."""
    h, li, pairs = mixer_half(w, x, layer_kind(a, i), a, mode)
    if i < a.first_dense:
        return dense_half(w, h, a, mode), li, pairs, None
    y, sent = expert_half(w, h, a, held, mode, cap)
    return y, li, pairs, sent


def head_loss(norm_w, head_w, x, labels, eps, mode=None, block=1024):
    """Mean next-token cross-entropy over x [B, T, H], labels [B, T]: a
    block of rows at a time."""
    B, T, H = x.shape
    xr = _rms(x[:, :-1], norm_w, eps).reshape(-1, H)
    tgt = labels[:, 1:].reshape(-1)
    n = xr.shape[0]
    if n % block:
        block = n

    def rows(args):
        xb, tb = args
        lg = _mm(xb, head_w, mode)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    parts = jax.lax.map(jax.checkpoint(rows),
                        (xr.reshape(-1, block, H), tgt.reshape(-1, block)))
    return jnp.sum(parts) / n


def _up(w):
    return {k: v.astype(F32) for k, v in w.items()}


def hidden_states(state, ids, cfg, held, mode=None):
    """Embedding then every layer: (x [B, T, H] before the last norm, the
    full layers' L_I summed, pairs attended per full layer, rows sent per
    expert layer)."""
    a = arch(cfg)
    x = jnp.take(state["model.embed_tokens"].astype(F32), ids, axis=0)
    li_sum, pairs, sent = jnp.zeros((), F32), [], []
    for i in range(cfg["num_hidden_layers"]):
        w = _up({k: state[n] for k, n in layer_names(a, i).items()})
        x, li, n_pairs, s = layer(w, x, i, a, held, mode)
        if layer_kind(a, i) == FULL:
            li_sum = li_sum + li
            pairs.append(n_pairs)
        if s is not None:
            sent.append(s)
    return x, li_sum, pairs, sent


def logits(state, ids, cfg, held, mode=None):
    a = arch(cfg)
    x = hidden_states(state, ids, cfg, held, mode)[0]
    return _mm(_rms(x, state["model.norm.weight"].astype(F32), a.eps),
               state["lm_head"].astype(F32), mode)


def losses(state, ids, cfg, held, mode=None):
    """(L_LM, sum over full layers of L_I)."""
    a = arch(cfg)
    x, li, _, _ = hidden_states(state, ids, cfg, held, mode)
    return head_loss(state["model.norm.weight"].astype(F32),
                     state["lm_head"].astype(F32), x, ids, a.eps, mode), li


def loss(state, ids, cfg, held, mode=None):
    lm, li = losses(state, ids, cfg, held, mode)
    return lm + arch(cfg).li_weight * li


def selected_sets(state, ids, cfg, held):
    """The selection masks [B, T, T] of every full layer, in order."""
    a = arch(cfg)
    x = jnp.take(state["model.embed_tokens"].astype(F32), ids, axis=0)
    out = []
    for i in range(cfg["num_hidden_layers"]):
        w = _up({k: state[n] for k, n in layer_names(a, i).items()})
        if layer_kind(a, i) == FULL:
            def one(xr):
                xn = _rms(xr, w["ln1"], a.eps)
                return full_mixer(_part(w, "mixer."), xn, a, None)[2]
            out.append(jax.lax.map(one, x))
        x = layer(w, x, i, a, held)[0]
    return out


def loss_and_grads(state, ids, cfg, held, which="total"):
    """(loss, {name: gradient}) of the whole model, by autodiff of the
    whole (small sizes: nothing is freed between layers). `which`: "total",
    "lm" (L_LM alone) or "indexer" (the L_I alone)."""
    pick = {"total": lambda s: loss(s, ids, cfg, held),
            "lm": lambda s: losses(s, ids, cfg, held)[0],
            "indexer": lambda s: losses(s, ids, cfg, held)[1]}[which]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(pick)(_up(state))


# -- the benchmark's own: training steps, half a layer at a time --------------


@functools.partial(jax.jit, static_argnames=("kind", "a", "mode"))
def _mixer_fwd(w, x, kind, a, mode):
    return mixer_half(_up(w), x, kind, a, mode)


@functools.partial(jax.jit, static_argnames=("kind", "a", "mode"))
def _mixer_bwd(w, x, dh, kind, a, mode):
    """VJP of (h, L_I) under (dh, li_weight): the indexer's leaves get
    their gradient from L_I here, every other leaf from dh."""
    def f(w_, x_):
        h, li, _ = mixer_half(w_, x_, kind, a, mode)
        return h, li

    _, vjp = jax.vjp(f, _up(w), x)
    return vjp((dh, jnp.asarray(a.li_weight, F32)))      # (dw, dx)


@functools.partial(jax.jit, static_argnames=("a", "held", "mode", "cap"))
def _expert_fwd(w, h, a, held, mode, cap):
    return expert_half(_up(w), h, a, held, mode, cap)


@functools.partial(jax.jit, static_argnames=("a", "held", "mode", "cap"))
def _expert_bwd(w, h, dy, a, held, mode, cap):
    _, vjp, _ = jax.vjp(
        lambda w_, h_: expert_half(w_, h_, a, held, mode, cap), _up(w), h,
        has_aux=True)
    return vjp(dy)                              # (dw, dh)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _dense_fwd(w, h, a, mode):
    return dense_half(_up(w), h, a, mode)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _dense_bwd(w, h, dy, a, mode):
    _, vjp = jax.vjp(lambda w_, h_: dense_half(w_, h_, a, mode), _up(w), h)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_loss(norm_w, head_w, x, labels, eps, mode):
    return jax.value_and_grad(
        lambda nw, hw, x_: head_loss(nw, hw, x_, labels, eps, mode),
        argnums=(0, 1, 2))(norm_w.astype(F32), head_w.astype(F32), x)


# programs `precompile` built ahead of time, by (function, static
# arguments, the arguments' shapes): `train_steps` runs these and asks
# the compile cache for nothing. (A lowering from shapes and the later
# call do not always meet in JAX's persistent cache: on the chip the
# reference compiled its seven programs twice, 78 s of a run that has
# 360; my chip runs, PR 33.)
_AOT = {}


def _signature(args):
    leaves, tree = jax.tree_util.tree_flatten(args)
    return tree, tuple((tuple(x.shape), str(x.dtype)) for x in leaves)


def _build(fn, statics, *args):
    """Compile jitted `fn(*args, **statics)` from shapes and keep it."""
    with jax.default_matmul_precision("highest"):
        exe = fn.lower(*args, **statics).compile()
    _AOT[(fn.__name__, tuple(sorted(statics.items())),
          _signature(args))] = exe


def _run(fn, statics, *args):
    """`fn(*args, **statics)` through the program `precompile` kept for
    these shapes, or through `jax.jit` where there is none."""
    exe = _AOT.get((fn.__name__, tuple(sorted(statics.items())),
                    _signature(args)))
    if exe is not None:
        try:
            return exe(*args)
        except (TypeError, ValueError):     # another layout than it was
            pass                            # built for: trace it instead
    return fn(*args, **statics)


def expert_cap(a, held, tokens):
    """Rows an expert may be sent before `train_steps` refuses to go on:
    four times a uniform router's share, never under 256."""
    return min(tokens, max(256, 4 * -(-tokens * a.top_k // a.n_routed)))


def _halves(names):
    """(the mixer half's keys, the feed-forward half's) of one layer."""
    return ([k for k in names if k == "ln1" or k.startswith("mixer.")],
            [k for k in names if k == "ln2" or k.startswith("mlp.")])


def precompile(shapes, cfg_json, batch, seq, mode=None):
    """Compile the programs `train_steps` will run on [batch, seq] tokens
    from `shapes` ({name: ShapeDtypeStruct} of the state) alone: each kind
    of half layer forward and VJP and the head + loss, into JAX's
    persistent compilation cache and into `_AOT`, where `train_steps`'
    own calls find them. Nothing runs and nothing is held on the
    device."""
    a, held = arch(cfg_json), held_of(cfg_json)
    cap = expert_cap(a, held, seq)
    x = jax.ShapeDtypeStruct((batch, seq, a.hidden), F32)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    jobs = [(_head_loss, dict(eps=a.eps, mode=mode),
             shapes["model.norm.weight"], shapes["lm_head"], x, ids)]
    seen = set()
    for i in range(cfg_json["num_hidden_layers"]):
        names = layer_names(a, i)
        w = {k: shapes[n] for k, n in names.items()}
        mixer, ffn = ({k: w[k] for k in part} for part in _halves(names))
        kind = layer_kind(a, i)
        if kind not in seen:
            st = dict(kind=kind, a=a, mode=mode)
            jobs += [(_mixer_fwd, st, mixer, x), (_mixer_bwd, st, mixer, x, x)]
        ffn_kind = "dense" if i < a.first_dense else "moe"
        if ffn_kind not in seen and ffn_kind == "moe":
            st = dict(a=a, held=held, mode=mode, cap=cap)
            jobs += [(_expert_fwd, st, ffn, x), (_expert_bwd, st, ffn, x, x)]
        elif ffn_kind not in seen:
            st = dict(a=a, mode=mode)
            jobs += [(_dense_fwd, st, ffn, x), (_dense_bwd, st, ffn, x, x)]
        seen.update((kind, ffn_kind))

    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        list(pool.map(lambda job: _build(*job), jobs))


def train_steps(make_state, batches, cfg_json, trainer, mode=None,
                keep=np.asarray):
    """Follow `len(batches)` training steps from the state `make_state()`
    returns, as `reference_solar_open2.train_steps` does: parameters and
    AdamW moments stored in the dtype the configuration trains in, all
    arithmetic float32, HALF a layer at a time, each half's input kept
    for the backward on the HOST (`keep`); a full layer's L_I is added to
    the loss where its layer is and its gradient taken with the mixer's.
    Returns {"losses" (L_LM + li_weight * sum L_I), "grad_norms",
    "delta_norms", "expert_rows" (the most rows any held expert was sent),
    "attended_pairs" (per full layer, last step)}."""
    a, held = arch(cfg_json), held_of(cfg_json)
    n_layers = cfg_json["num_hidden_layers"]
    hp = (float(trainer["beta1"]), float(trainer["beta2"]),
          float(trainer["epsilon"]), float(trainer["weight_decay"]))
    lr = np.float32(trainer["learning_rate"])
    p = make_state()
    names = [layer_names(a, i) for i in range(n_layers)]
    trained = {"model.embed_tokens", "model.norm.weight", "lm_head"}
    trained.update(n for per in names for n in per.values()
                   if not n.endswith(BUFFERS))
    mom, losses, grad_norms, most, pairs = {}, [], {}, 0, []

    def half(i, which):
        """The weights one half of layer i reads: its norm and its part."""
        return {k: p[names[i][k]] for k in _halves(names[i])[which]}

    def update(name, g, t):
        if name not in trained:
            return
        m, v = mom.pop(name, None) or (jnp.zeros_like(p[name]),
                                       jnp.zeros_like(p[name]))
        p[name], m, v, gn = _adamw(p[name], m, v, g, lr, np.float32(t), hp)
        mom[name] = (m, v)
        if t == 1:
            grad_norms[name] = gn

    with jax.default_matmul_precision("highest"):
        for t, ids in enumerate(jnp.asarray(batches), start=1):
            cap = expert_cap(a, held, ids.shape[1])
            moe_st = dict(a=a, held=held, mode=mode, cap=cap)
            x = _embed(p["model.embed_tokens"], ids)
            xs, li_sum, pairs = [], 0.0, []
            for i in range(n_layers):
                kind = layer_kind(a, i)
                xs.append(keep(x))
                x, li, n_pairs = _run(_mixer_fwd, dict(kind=kind, a=a, mode=mode),
                                     half(i, 0), x)
                if kind == FULL:
                    li_sum = li_sum + li
                    pairs.append(int(n_pairs))
                xs.append(keep(x))
                if i < a.first_dense:
                    x = _run(_dense_fwd, dict(a=a, mode=mode), half(i, 1), x)
                    continue
                x, sent = _run(_expert_fwd, moe_st, half(i, 1), x)
                most = max(most, int(jnp.max(sent)))
                if most > cap:
                    raise AssertionError(
                        f"reference: an expert of layer {i} was sent {most} "
                        f"rows, more than the {cap} it multiplies")
            lm, (dn, dh, dx) = _run(
                _head_loss, dict(eps=a.eps, mode=mode),
                p["model.norm.weight"], p["lm_head"], x, ids)
            del x
            losses.append(lm + a.li_weight * li_sum)
            update("model.norm.weight", dn, t)
            update("lm_head", dh, t)
            for i in reversed(range(n_layers)):
                h_in = jnp.asarray(xs.pop())
                if i < a.first_dense:
                    dw, dx = _run(_dense_bwd, dict(a=a, mode=mode), half(i, 1),
                                  h_in, dx)
                else:
                    dw, dx = _run(_expert_bwd, moe_st, half(i, 1), h_in, dx)
                for k, g in dw.items():
                    update(names[i][k], g, t)
                dw, dx = _run(
                    _mixer_bwd, dict(kind=layer_kind(a, i), a=a, mode=mode),
                    half(i, 0), jnp.asarray(xs.pop()), dx)
                for k, g in dw.items():
                    update(names[i][k], g, t)
            update("model.embed_tokens",
                   _embed_grad(p["model.embed_tokens"], ids, dx), t)
        del mom, xs, dx, dw, dn, dh
        start = make_state()
        delta = {k: _diff_norm(p[k], start[k]) for k in sorted(trained)}
    return {"losses": [float(x) for x in losses],
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()},
            "expert_rows": most, "attended_pairs": pairs}
