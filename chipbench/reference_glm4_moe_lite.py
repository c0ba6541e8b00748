"""The plain reference of the glm4_moe_lite language model (GLM-4.7-Flash):
a pre-norm decoder whose attention is latent (MLA) and dense causal in
every layer, whose feed-forward is a dense SwiGLU in the first
`first_k_dense_replace` layers and a sigmoid-routed mixture of experts with
one shared expert after, and whose loss has a multi-token-prediction module
of depth 1. Written from the equations in `jax.numpy` float32 at matmul
precision "highest": no kernel, nothing imported from the program (the
control's rounding and the optimizer are `chipbench/reference.py`'s). The
CPU tests hold `paddle_tpu/models/glm4_moe_lite.py` to this file, and
`train_steps` below decides the benchmark cell's `correct`.

Equations (x the layer input, one sequence, eps the config's):
  block   h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
  MLA     (n heads, d_n no-rope, d_r rope, d_v value, ranks r_q, r_kv, theta)
          c^Q = RMSNorm(x W_DQ);  [q^N_h | q^R_h] = c^Q W_UQ,h;
          [c^KV | k^R] = x W_DKV;  c^KV <- RMSNorm(c^KV);
          q^R, k^R <- RoPE_theta (rotate-half pairing; k^R one for all heads);
          [k^N_h | v_h] = c^KV W_UKV,h;
          a_tsh = (q^N.k^N + q^R.k^R) / sqrt(d_n + d_r);
          o_th = sum_{s <= t} softmax_{s <= t}(a_t.h) v_sh;  out_t = [o_th]_h W_O
  MoE     s = sigmoid(x Wr) over ALL experts; top-k by s + b (b a buffer;
          n_group 1, topk_group 1: one group, nothing to limit);
          w = s_top / sum(s_top) * routed_scaling_factor;
          y = Shared(x) + sum_k w_k E_k(x), E and Shared SwiGLU.
  loss    L_main = mean_{i <= T-2} CE(RMSNorm_f(h^L_i) W_head, t_{i+1})
  MTP     u_i = [RMSNorm_e(Emb t_{i+1}) ; RMSNorm_h(h^L_i)] W_EH, h^L before
          the final norm; g = one whole expert layer of its own over u
          (causal, rotary positions i);
          L_MTP = mean_{i <= T-3} CE(RMSNorm_s(g_i) W_head, t_{i+2});
          Emb and W_head are the trunk's own leaves;
          loss = L_main + mtp_loss_weight * L_MTP.
`held = (e0, n)`: the experts [e0, e0 + n) live here, the router keeps
every output and its top-k, and a pair routed to an absent expert adds
nothing. held = (0, n_routed) is the uncut layer.

Departures from the published description:
  * the module runs on all T positions so that every block keeps its
    shape: position T-1 is fed id 0 in place of t_T, which does not exist,
    and is masked out of L_MTP (causal: nothing before it reads it);
  * the order inside [e ; h] is embedding first (a row permutation of W_EH);
  * rotary tables are made on the host from float64 angles;
  * because plain f32 at the benchmark's sizes would not fit one chip, none
    changing a value: attention runs one group of four heads and one block
    of query rows at a time, a block reading the keys up to the end of its
    eighth of the sequence; head and loss run a block of rows at a time over
    all T rows with the rows that have no label masked; backward passes
    recompute inside blocks; an expert multiplies only the (at most `cap`)
    rows routed to it; `train_steps` goes half a layer at a time.

State-dict layout (matrices [in, out]): `q_b_proj` holds a head's
[q^N | q^R] columns side by side, head after head; `kv_b_proj` a head's
[k^N | v]; `kv_a_proj` [c^KV | k^R]; `*gate_up*` gate | up columns; expert
stacks are [n_held, ...]; `mtp.eh_proj` [2H, H], rows [e ; h].

`mode` computes every weight matmul but the router's in a lower precision
(the control of `correct`): "fp8" (e4m3, per-row / per-column scales),
"int8" or "bf16"; the gradient passes straight through.
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
CAUSAL = "causal_attention"
BANDS = 8                  # key prefixes a sequence's row blocks read
ROWS = 256                 # query rows of one attention block


class Arch(NamedTuple):
    hidden: int
    eps: float
    layers: int
    first_dense: int
    n: int             # heads
    dn: int
    dr: int
    dv: int
    rq: int
    rkv: int
    theta: float
    m: int             # expert width
    n_routed: int
    top_k: int
    norm_topk: bool
    scaling: float
    mtp_weight: float


def arch(cfg):
    """The static sizes the equations need, from a configuration file."""
    return Arch(
        hidden=cfg["hidden_size"], eps=float(cfg["rms_norm_eps"]),
        layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        n=cfg["num_attention_heads"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        rq=cfg["q_lora_rank"], rkv=cfg["kv_lora_rank"],
        theta=float(cfg["rope_theta"]), m=cfg["moe_intermediate_size"],
        n_routed=cfg.get("reduced_from", {}).get("n_routed_experts",
                                                 cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]),
        mtp_weight=float(cfg["mtp_loss_weight"])
        if cfg.get("num_nextn_predict_layers", 0) else 0.0)


def held_of(cfg):
    """(first expert, experts) this configuration holds of each layer."""
    return cfg.get("expert_offset", 0), cfg["n_routed_experts"]


def layer_kind(a, i):
    """Every layer is of one kind (`rehearse_pretrain.py` asks)."""
    return CAUSAL


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
    and a chip's float32 power, sine and cosine are not exact there."""
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


# -- latent attention ----------------------------------------------------------

def _latents(w, xn, a, mode):
    """(c^Q [T, r_q], c^KV [T, r_kv], rotated k^R [T, d_r])."""
    cq = _rms(_mm(xn, w["q_a_proj"], mode), w["q_a_layernorm.weight"], a.eps)
    down = _mm(xn, w["kv_a_proj"], mode)
    ckv = _rms(down[:, :a.rkv], w["kv_a_layernorm.weight"], a.eps)
    return cq, ckv, _rope(down[:, a.rkv:], a.theta)


def _head_groups(a):
    """Groups the heads are handled in: 4 heads each where they divide."""
    return a.n // 4 if a.n % 4 == 0 else 1


def _grouped(w, a):
    """The up-projections and W_O by group of heads."""
    G = _head_groups(a)
    hg = a.n // G
    return {
        "uq": w["q_b_proj"].reshape(a.rq, G, hg * (a.dn + a.dr)).transpose(
            1, 0, 2),
        "ukv": w["kv_b_proj"].reshape(a.rkv, G, hg * (a.dn + a.dv)
                                      ).transpose(1, 0, 2),
        "o": w["o_proj"].reshape(G, hg * a.dv, -1),
    }


def _group_qkv(cq, ckv, kr, wg, a, mode):
    """A group's q [T, hg, d_n + d_r] (rope applied), k likewise (k^R
    repeated), v [T, hg, d_v]."""
    T = cq.shape[0]
    q = _mm(cq, wg["uq"], mode).reshape(T, -1, a.dn + a.dr)
    q = jnp.concatenate([q[..., :a.dn], _rope(q[..., a.dn:], a.theta)], -1)
    kv = _mm(ckv, wg["ukv"], mode).reshape(T, -1, a.dn + a.dv)
    hg = kv.shape[1]
    key = jnp.concatenate(
        [kv[..., :a.dn], jnp.broadcast_to(kr[:, None], (T, hg, a.dr))], -1)
    return q, key, kv[..., a.dn:]


def _attend(q, key, v):
    """o [T, hg, dv]: causal softmax attention, a block of query rows at a
    time; the blocks of the j-th of `BANDS` stretches of the sequence read
    the keys up to that stretch's end (a static prefix each: what lies
    beyond a row's own position is masked)."""
    T, d = q.shape[0], q.shape[-1]
    block, bands = (T, 1) if T % (BANDS * ROWS) else (ROWS, BANDS)

    def rows(i, n_keys):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        t = i * block + jnp.arange(block)
        ok = jnp.arange(n_keys)[None] <= t[:, None]
        s = jnp.einsum("thd,shd->hts", qs, key[:n_keys],
                       precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(ok[None], s, NEG), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v[:n_keys], precision=HI)

    per = T // block // bands
    out = [jax.lax.map(
        jax.checkpoint(functools.partial(rows, n_keys=(j + 1) * per * block)),
        j * per + jnp.arange(per)) for j in range(bands)]
    return jnp.concatenate(out, 0).reshape((T,) + out[0].shape[2:])


def _mixer(w, lat, a, mode):
    """Attention output [T, H] from the latents, one group of heads at a
    time."""
    def group(lat, wg):
        q, key, v = _group_qkv(*lat, wg, a, mode)
        o = _attend(q, key, v)
        return _mm(o.reshape(o.shape[0], -1), wg["o"], mode)

    def body(acc, wg):
        return acc + jax.checkpoint(group)(lat, wg), None

    out, _ = jax.lax.scan(
        body, jnp.zeros((lat[0].shape[0], w["o_proj"].shape[1]), F32),
        _grouped(w, a))
    return out


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


def _moe(w, xn, a, held, mode, cap, shared=True):
    """Shared expert + the held experts' part. Returns (y, rows sent to
    each held expert [n]); `shared` False leaves the shared expert out (a
    share's part alone)."""
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

    first = (_swiglu(xn, w["shared_gate_up"], w["shared_down"], mode)
             if shared else jnp.zeros_like(xn))
    y, sent = jax.lax.scan(
        body, first, (e0 + jnp.arange(n), {"gu": w["experts_gate_up"],
                                           "down": w["experts_down"]}))
    return y, sent


# -- a layer, the head, the module, the whole ----------------------------------

_MLA = ("q_a_proj", "q_a_layernorm.weight", "q_b_proj", "kv_a_proj",
        "kv_a_layernorm.weight", "kv_b_proj", "o_proj")
_MOE = ("router", "experts_gate_up", "experts_down", "shared_gate_up",
        "shared_down", "e_score_correction_bias")
_DENSE = ("gate_up_proj", "down_proj")
BUFFERS = ("e_score_correction_bias",)       # in the state, never trained
MTP = "mtp"                                  # `layer_names`' key of the module
_JOIN = {"enorm": "mtp.enorm.weight", "hnorm": "mtp.hnorm.weight",
         "eh": "mtp.eh_proj"}


def layer_names(a, i):
    """{key the equations use: state-dict name} of layer i, or of the
    prediction module's expert layer (i = `MTP` or the layer count)."""
    mtp = i == MTP or i == a.layers
    base = "mtp.block." if mtp else f"model.layers.{i}."
    names = {"ln1": base + "input_layernorm.weight",
             "ln2": base + "post_attention_layernorm.weight"}
    names.update({"mixer." + k: base + "self_attn." + k for k in _MLA})
    names.update({"mlp." + k: base + "mlp." + k
                  for k in (_MOE if mtp or i >= a.first_dense else _DENSE)})
    return names


def _part(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def mixer_half(w, x, a, mode=None):
    """h = x + Attn(RMSNorm(x)) on x [B, T, H] float32."""
    def one(xr):
        m = _part(w, "mixer.")
        xn = _rms(xr, w["ln1"], a.eps)
        return xr + _mixer(m, _latents(m, xn, a, mode), a, mode)

    return jax.lax.map(one, x)


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
    """Layer i (or the module's, i = `MTP`) on x [B, T, H] float32: (y,
    rows sent to each held expert [B, n] or None)."""
    h = mixer_half(w, x, a, mode)
    if i != MTP and i < a.first_dense:
        return dense_half(w, h, a, mode), None
    return expert_half(w, h, a, held, mode, cap)


def targets(ids, shift):
    """Row i's label t_{i + shift}, -1 on the last `shift` rows."""
    return jnp.concatenate(
        [ids[:, shift:], jnp.full((ids.shape[0], shift), -1, ids.dtype)], 1)


def head_loss(norm_w, head_w, x, tgt, eps, mode=None, block=1024):
    """Mean cross-entropy of RMSNorm(x) W_head over the rows of x
    [B, T, H] whose target in tgt [B, T] is not -1: a block of rows at a
    time."""
    H = x.shape[-1]
    xr, tg = _rms(x, norm_w, eps).reshape(-1, H), tgt.reshape(-1)
    n = xr.shape[0]
    if n % block:
        block = n

    def rows(args):
        xb, tb = args
        lg = _mm(xb, head_w, mode)
        lse = jax.nn.logsumexp(lg, axis=-1)
        got = jnp.take_along_axis(lg, jnp.maximum(tb, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(tb >= 0, lse - got, 0.0))

    parts = jax.lax.map(jax.checkpoint(rows),
                        (xr.reshape(-1, block, H), tg.reshape(-1, block)))
    return jnp.sum(parts) / jnp.sum(tg >= 0)


def next_ids(ids):
    """The ids one position on, id 0 where the sequence ends."""
    return jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], 1)


def join(w, table, x, ids, eps, mode=None):
    """u = [RMSNorm_e(Emb t_{i+1}) ; RMSNorm_h(x_i)] W_EH on x [B, T, H]."""
    e = jnp.take(table, next_ids(ids), axis=0)
    return _mm(jnp.concatenate([_rms(e, w["enorm"], eps),
                                _rms(x, w["hnorm"], eps)], -1), w["eh"], mode)


def _up(w):
    return {k: v.astype(F32) for k, v in w.items()}


def _layer_w(state, a, i):
    return _up({k: state[n] for k, n in layer_names(a, i).items()})


def hidden_states(state, ids, cfg, held, mode=None):
    """Embedding then every layer: (x [B, T, H] before the last norm, rows
    sent per expert layer)."""
    a = arch(cfg)
    x = jnp.take(state["model.embed_tokens"].astype(F32), ids, axis=0)
    sent = []
    for i in range(a.layers):
        x, s = layer(_layer_w(state, a, i), x, i, a, held, mode)
        if s is not None:
            sent.append(s)
    return x, sent


def logits(state, ids, cfg, held, mode=None):
    a = arch(cfg)
    x = hidden_states(state, ids, cfg, held, mode)[0]
    return _mm(_rms(x, state["model.norm.weight"].astype(F32), a.eps),
               state["lm_head"].astype(F32), mode)


def module_states(state, x, ids, cfg, held, mode=None):
    """g [B, T, H]: the prediction module's expert layer over u, before
    its final norm, from the trunk's last hidden states x."""
    a = arch(cfg)
    u = join(_up({k: state[n] for k, n in _JOIN.items()}),
             state["model.embed_tokens"].astype(F32), x, ids, a.eps, mode)
    return layer(_layer_w(state, a, MTP), u, MTP, a, held, mode)[0]


def losses(state, ids, cfg, held, mode=None):
    """(L_main, L_MTP); L_MTP is 0 for a configuration without module."""
    a = arch(cfg)
    x = hidden_states(state, ids, cfg, held, mode)[0]
    head = state["lm_head"].astype(F32)
    main = head_loss(state["model.norm.weight"].astype(F32), head, x,
                     targets(ids, 1), a.eps, mode)
    if not cfg.get("num_nextn_predict_layers", 0):
        return main, jnp.zeros((), F32)
    g = module_states(state, x, ids, cfg, held, mode)
    return main, head_loss(state["mtp.norm.weight"].astype(F32), head, g,
                           targets(ids, 2), a.eps, mode)


def loss(state, ids, cfg, held, mode=None, mtp_weight=None):
    main, extra = losses(state, ids, cfg, held, mode)
    w = arch(cfg).mtp_weight if mtp_weight is None else mtp_weight
    return main + w * extra


def loss_and_grads(state, ids, cfg, held, which="total", mtp_weight=None):
    """(loss, {name: gradient}) of the whole model, by autodiff of the
    whole (small sizes: nothing is freed between layers). `which`: "total",
    "main" (L_main alone), "mtp" (L_MTP alone, unweighted), or "all": the
    three as {which: (loss, gradients)} from one forward pass."""
    w = arch(cfg).mtp_weight if mtp_weight is None else mtp_weight

    @jax.jit
    def both(s):
        (main, extra), vjp = jax.vjp(lambda s_: losses(s_, ids, cfg, held), s)
        one, zero = jnp.ones((), F32), jnp.zeros((), F32)
        return main, extra, vjp((one, zero))[0], vjp((zero, one))[0]

    with jax.default_matmul_precision("highest"):
        main, extra, g_main, g_extra = both(_up(state))
    out = {"main": (main, g_main), "mtp": (extra, g_extra),
           "total": (main + w * extra, jax.tree_util.tree_map(
               lambda a_, b_: a_ + w * b_, g_main, g_extra))}
    return out if which == "all" else out[which]


# -- the benchmark's own: training steps, half a layer at a time --------------


# `kind` is the one kind there is: the call form `rehearse_pretrain.py`
# has for every reference of this family
@functools.partial(jax.jit, static_argnames=("kind", "a", "mode"))
def _mixer_fwd(w, x, kind, a, mode):
    return mixer_half(_up(w), x, a, mode)


@functools.partial(jax.jit, static_argnames=("kind", "a", "mode"))
def _mixer_bwd(w, x, dh, kind, a, mode):
    _, vjp = jax.vjp(lambda w_, x_: mixer_half(w_, x_, a, mode), _up(w), x)
    return vjp(dh)                              # (dw, dx)


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
def _join_fwd(w, table, x, ids, eps, mode):
    return join(_up(w), table.astype(F32), x, ids, eps, mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _join_bwd(w, table, x, ids, du, eps, mode):
    """(dw, the embedding table's gradient from this use, dx)."""
    _, vjp = jax.vjp(lambda w_, t_, x_: join(w_, t_, x_, ids, eps, mode),
                     _up(w), table.astype(F32), x)
    return vjp(du)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_loss(norm_w, head_w, x, tgt, eps, mode):
    return jax.value_and_grad(
        lambda nw, hw, x_: head_loss(nw, hw, x_, tgt, eps, mode),
        argnums=(0, 1, 2))(norm_w.astype(F32), head_w.astype(F32), x)


# programs `precompile` built ahead of time, by (function, static
# arguments, the arguments' shapes): `train_steps` runs these and asks the
# compile cache for nothing (as `reference_dots3_note.py`, which says why)
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
    eight times a uniform router's share, never under 256 (a seeded router
    at 64 experts top-4 sent one expert 4,161 of 16,384 tokens, four times
    the share; my chip run, PR 44)."""
    return min(tokens, max(256, 8 * -(-tokens * a.top_k // a.n_routed)))


def _halves(names):
    """(the mixer half's keys, the feed-forward half's) of one layer."""
    return ([k for k in names if k == "ln1" or k.startswith("mixer.")],
            [k for k in names if k == "ln2" or k.startswith("mlp.")])


def precompile(shapes, cfg_json, batch, seq, mode=None):
    """Compile the programs `train_steps` will run on [batch, seq] tokens
    from `shapes` ({name: ShapeDtypeStruct} of the state) alone: the mixer
    half, the expert half and the dense half forward and VJP, the module's
    join forward and VJP, the head + loss, into JAX's persistent
    compilation cache and into `_AOT`, where `train_steps`' own calls find
    them. Nothing runs and nothing is held on the device."""
    a, held = arch(cfg_json), held_of(cfg_json)
    cap = expert_cap(a, held, seq)
    x = jax.ShapeDtypeStruct((batch, seq, a.hidden), F32)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    jobs = [(_head_loss, dict(eps=a.eps, mode=mode),
             shapes["model.norm.weight"], shapes["lm_head"], x, ids)]
    module = bool(cfg_json.get("num_nextn_predict_layers", 0))
    if module:
        w = {k: shapes[n] for k, n in _JOIN.items()}
        st = dict(eps=a.eps, mode=mode)
        table = shapes["model.embed_tokens"]
        jobs += [(_join_fwd, st, w, table, x, ids),
                 (_join_bwd, st, w, table, x, ids, x)]

    def halves(i):
        names = layer_names(a, i)
        return ({k: shapes[names[k]] for k in part}
                for part in _halves(names))

    mixer, ffn = halves(MTP if module else a.layers - 1)
    st = dict(kind=CAUSAL, a=a, mode=mode)
    jobs += [(_mixer_fwd, st, mixer, x), (_mixer_bwd, st, mixer, x, x)]
    if module or a.first_dense < a.layers:
        st = dict(a=a, held=held, mode=mode, cap=cap)
        jobs += [(_expert_fwd, st, ffn, x), (_expert_bwd, st, ffn, x, x)]
    if a.first_dense:
        st, (_, ffn) = dict(a=a, mode=mode), halves(0)
        jobs += [(_dense_fwd, st, ffn, x), (_dense_bwd, st, ffn, x, x)]

    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        list(pool.map(lambda job: _build(*job), jobs))


def train_steps(make_state, batches, cfg_json, trainer, mode=None,
                keep=np.asarray):
    """Follow `len(batches)` training steps from the state `make_state()`
    returns: parameters and AdamW moments stored in the dtype the
    configuration trains in, all arithmetic float32, HALF a layer at a
    time, each half's input kept for the backward on the HOST (`keep`).
    After the trunk the module runs on the trunk's last hidden states; its
    cotangent to them joins the main head's, the head's two gradients and
    the embedding's two are summed before their one update. `trainer` may
    carry `mtp_loss_weight` (a fault of `correct.faults` sets it to 0: the
    module's leaves then see a zero gradient and weight decay alone).
    Returns {"losses" (L_main + weight * L_MTP), "main_losses",
    "mtp_losses", "grad_norms", "delta_norms", "expert_rows" (the most rows
    any held expert was sent)}."""
    a, held = arch(cfg_json), held_of(cfg_json)
    module = bool(cfg_json.get("num_nextn_predict_layers", 0))
    lam = np.float32(trainer.get("mtp_loss_weight", a.mtp_weight))
    hp = (float(trainer["beta1"]), float(trainer["beta2"]),
          float(trainer["epsilon"]), float(trainer["weight_decay"]))
    lr = np.float32(trainer["learning_rate"])
    p = make_state()
    blocks = list(range(a.layers)) + ([MTP] if module else [])
    names = {i: layer_names(a, i) for i in blocks}
    trained = {"model.embed_tokens", "model.norm.weight", "lm_head"}
    trained.update(n for per in names.values() for n in per.values()
                   if not n.endswith(BUFFERS))
    if module:
        trained.update(_JOIN.values())
        trained.add("mtp.norm.weight")
    mom, losses, mains, extras, grad_norms, most = {}, [], [], [], {}, 0
    mix_st = dict(kind=CAUSAL, a=a, mode=mode)
    head_st = dict(eps=a.eps, mode=mode)

    def half(i, which):
        """The weights one half of block i reads: its norm and its part."""
        return {k: p[names[i][k]] for k in _halves(names[i])[which]}

    def update(name, g, t):
        m, v = mom.pop(name, None) or (jnp.zeros_like(p[name]),
                                       jnp.zeros_like(p[name]))
        p[name], m, v, gn = _adamw(p[name], m, v, g, lr, np.float32(t), hp)
        mom[name] = (m, v)
        if t == 1:
            grad_norms[name] = gn

    def forward(i, x, xs, moe_st):
        """Block i on x, both halves' inputs kept on `xs`."""
        nonlocal most
        xs.append(keep(x))
        x = _run(_mixer_fwd, mix_st, half(i, 0), x)
        xs.append(keep(x))
        if i != MTP and i < a.first_dense:
            return _run(_dense_fwd, dict(a=a, mode=mode), half(i, 1), x)
        x, sent = _run(_expert_fwd, moe_st, half(i, 1), x)
        most = max(most, int(jnp.max(sent)))
        if most > moe_st["cap"]:
            raise AssertionError(
                f"reference: an expert of block {i} was sent {most} rows, "
                f"more than the {moe_st['cap']} it multiplies")
        return x

    def backward(i, dx, xs, moe_st, t):
        """Block i's VJP under dx; its leaves are updated here."""
        h_in = jnp.asarray(xs.pop())
        if i != MTP and i < a.first_dense:
            dw, dx = _run(_dense_bwd, dict(a=a, mode=mode), half(i, 1),
                          h_in, dx)
        else:
            dw, dx = _run(_expert_bwd, moe_st, half(i, 1), h_in, dx)
        for k, g in dw.items():
            if not names[i][k].endswith(BUFFERS):
                update(names[i][k], g, t)
        dw, dx = _run(_mixer_bwd, mix_st, half(i, 0), jnp.asarray(xs.pop()),
                      dx)
        for k, g in dw.items():
            update(names[i][k], g, t)
        return dx

    with jax.default_matmul_precision("highest"):
        for t, ids in enumerate(jnp.asarray(batches), start=1):
            moe_st = dict(a=a, held=held, mode=mode,
                          cap=expert_cap(a, held, ids.shape[1]))
            x = _embed(p["model.embed_tokens"], ids)
            xs = []
            for i in range(a.layers):
                x = forward(i, x, xs, moe_st)
            main, (dn, dhead, dx) = _run(
                _head_loss, head_st, p["model.norm.weight"], p["lm_head"], x,
                targets(ids, 1))
            update("model.norm.weight", dn, t)
            extra, dtable = jnp.zeros((), F32), None
            if module:
                jw = {k: p[n] for k, n in _JOIN.items()}
                ys = []
                u = _run(_join_fwd, head_st, jw, p["model.embed_tokens"], x,
                         ids)
                x_last = keep(x)
                del x
                g = forward(MTP, u, ys, moe_st)
                del u
                extra, (dn, dh2, dg) = _run(
                    _head_loss, head_st, p["mtp.norm.weight"], p["lm_head"],
                    g, targets(ids, 2))
                del g
                update("mtp.norm.weight", lam * dn, t)
                dhead = dhead + lam * dh2
                du = backward(MTP, lam * dg, ys, moe_st, t)
                dw, dtable, dx2 = _run(
                    _join_bwd, head_st, jw, p["model.embed_tokens"],
                    jnp.asarray(x_last), ids, du)
                for k, gk in dw.items():
                    update(_JOIN[k], gk, t)
                dx = dx + dx2
                del du, dx2, dh2, dg, x_last, jw
            else:
                del x
            update("lm_head", dhead, t)
            mains.append(main)
            extras.append(extra)
            losses.append(main + lam * extra)
            for i in reversed(range(a.layers)):
                dx = backward(i, dx, xs, moe_st, t)
            g_table = _embed_grad(p["model.embed_tokens"], ids, dx)
            update("model.embed_tokens",
                   g_table if dtable is None else g_table + dtable, t)
        del mom, xs, dx, dn, dhead, g_table, dtable
        start = make_state()
        delta = {k: _diff_norm(p[k], start[k]) for k in sorted(trained)}
    return {"losses": [float(x) for x in losses],
            "main_losses": [float(x) for x in mains],
            "mtp_losses": [float(x) for x in extras],
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()},
            "expert_rows": most}
