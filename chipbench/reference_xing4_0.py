"""The plain reference of the xing4_0 language model (Xing4.0-29B-A4B): a
pre-norm decoder whose residual path is n streams wide
(manifold-constrained hyper-connections, arXiv:2512.24880, after
arXiv:2409.19606) around latent attention (MLA, dense causal, rotary under
YaRN), a dense SwiGLU in the first `first_k_dense_replace` layers and a
sigmoid-routed mixture of experts with one shared expert after, and a
multi-token-prediction module of depth 1 in the loss. Written from the
equations in `jax.numpy` float32 at matmul precision "highest": no kernel,
nothing imported from the program. What is the same mathematics as
GLM-4.7-Flash's (the norm, SwiGLU, the causal core a block of rows at a
time, the router and the experts, head + loss, the module's join, the
compiled-ahead programs) is `chipbench/reference_glm4_moe_lite.py`'s; the
optimizer and the control's rounding are `chipbench/reference.py`'s. The CPU
tests hold `paddle_tpu/models/xing4_0.py` to this file, and `train_steps`
below decides the benchmark cell's `correct`.

Equations (n = hc_mult streams, C = hidden_size; per token X in R^{n x C}):
  entry   X_0[j] = Emb(t) for every j
  half    x~ = vec(X) (stream after stream), r = rsqrt(mean(x~^2) + eps),
          m = r (x~ Phi), Phi [nC, 2n + n^2];
          H~_pre = a_pre m[0:n] + b_pre;  H~_post = a_post m[n:2n] + b_post;
          H~_res = a_res reshape(m[2n:], n, n) + b_res;
          H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post);
          M_0 = exp(clamp(H~_res, lo, hi)); `hc_sinkhorn_iters` times:
          M <- M / (row sums + hc_eps), M <- M / (column sums + hc_eps);
          H_res = the last M;
          u = sum_j H_pre[j] X[j];  y = F(u);
          X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
          F = Attn(RMSNorm_in(u)) or FFN(RMSNorm_post(u)), no add inside
  exit    x = sum_j X_L[j], then the final RMSNorm
  MLA     as `reference_glm4_moe_lite.py`, but the rotary: f_i =
          theta^(-2i/d); dim(b) = d ln(L0 / (2 pi b)) / (2 ln theta);
          low = max(floor(dim(beta_fast)), 0), high = min(ceil(dim(
          beta_slow)), d/2 - 1); ramp_i = clip((i - low) / (high - low), 0,
          1); inv_freq_i = f_i (1 - ramp_i) + (f_i / s) ramp_i; cos and sin
          times mscale(s, mscale) / mscale(s, mscale_all_dim); the softmax
          scale (d_n + d_r)^-0.5 mscale(s, mscale_all_dim)^2;
          mscale(s, m) = 0.1 m ln s + 1
  MoE, loss, MTP  as `reference_glm4_moe_lite.py`; the module reads the
          REDUCED trunk output before the final norm, its u is expanded to
          n streams, goes through one whole n-stream expert layer and is
          reduced by the sum.
X is laid out [B, n, T, C]. `held = (e0, n)` as in the GLM reference.

Where the streams are copies of one another (the trunk's first half-layer
and the module's: their input is an expansion), two of the three maps move
nothing: u = (sum_j H_pre[j]) x is x up to a factor the branch's norm
divides out, and H_res X = (row sums) x with the rows summing to 1. The
gradients of those leaves' H_pre and H_res parts are zero in exact
arithmetic (1e-9 here), and rounding noise a hundredth of a real gradient
in a bfloat16 program, which AdamW's first steps turn into steps of the
full rate once it is over epsilon. `train_steps` therefore leaves the
leaves `entry_path_leaves` names out of `delta_norms` (their first
gradient's norm, which the H_post part decides, is compared like any
other's): PERF.md section 7 says what `correct` cannot see for it.

`mode` computes every weight matmul but the router's in a lower precision
(the control of `correct`), the product with Phi among them; the maps'
own arithmetic (sigmoids, Sinkhorn) and the mixes stay float32.
"""
from __future__ import annotations

import concurrent.futures
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference_glm4_moe_lite as glm
from chipbench.reference import _adamw, _diff_norm, _embed, _embed_grad
from chipbench.reference_glm4_moe_lite import (  # noqa: F401
    _build, _mm, _part, _rms, _run, _swiglu, _up, expert_cap, head_loss,
    held_of, join, next_ids, targets)

F32 = jnp.float32
CAUSAL = "causal_attention"
MTP = glm.MTP
BUFFERS = glm.BUFFERS
_JOIN = glm._JOIN
_HC = ("phi", "scale", "bias")


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
    yarn: tuple        # (factor, L0, beta_fast, beta_slow, mscale,
    m: int             # mscale_all_dim) or None; m: expert width
    n_routed: int
    top_k: int
    norm_topk: bool
    scaling: float
    mtp_weight: float
    streams: int
    hc_iters: int
    hc_eps: float
    clamp: tuple


def arch(cfg):
    """The static sizes the equations need, from a configuration file."""
    rs = cfg.get("rope_scaling")
    if rs is not None and rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling of type {rs.get('type')!r}")
    return Arch(
        hidden=cfg["hidden_size"], eps=float(cfg["rms_norm_eps"]),
        layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        n=cfg["num_attention_heads"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        rq=cfg["q_lora_rank"], rkv=cfg["kv_lora_rank"],
        theta=float(cfg["rope_theta"]),
        yarn=None if rs is None else (
            float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs["beta_fast"]), float(rs["beta_slow"]),
            float(rs["mscale"]), float(rs["mscale_all_dim"])),
        m=cfg["moe_intermediate_size"],
        n_routed=cfg.get("reduced_from", {}).get("n_routed_experts",
                                                 cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]),
        mtp_weight=float(cfg["mtp_loss_weight"])
        if cfg.get("num_nextn_predict_layers", 0) else 0.0,
        streams=cfg["hc_mult"], hc_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        clamp=(float(cfg["mhc_h_res_clamp_min"]),
               float(cfg["mhc_h_res_clamp_max"])))


def layer_kind(a, i):
    """Every layer is of one kind (`rehearse_pretrain.py` asks)."""
    return CAUSAL


# -- rotary under YaRN ---------------------------------------------------------

def mscale(s, m):
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn_inv_freq(d, theta, yarn):
    """The d / 2 frequencies, float64 (the module docstring's formula)."""
    f = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if yarn is None:
        return f
    s, L0, fast, slow = yarn[:4]
    dim = lambda b: d * math.log(L0 / (2 * math.pi * b)) / (
        2 * math.log(theta))
    low = max(math.floor(dim(fast)), 0)
    high = min(math.ceil(dim(slow)), d // 2 - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / s) * ramp


def rope_tables(T, d, a):
    """cos and sin [T, d/2] float32, made on the host in float64."""
    ang = np.arange(T, dtype=np.float64)[:, None] * yarn_inv_freq(
        d, a.theta, a.yarn)[None]
    k = 1.0 if a.yarn is None else (mscale(a.yarn[0], a.yarn[4])
                                    / mscale(a.yarn[0], a.yarn[5]))
    return ((np.cos(ang) * k).astype(np.float32),
            (np.sin(ang) * k).astype(np.float32))


def softmax_factor(a):
    """What the scores are multiplied by besides (d_n + d_r)^-0.5."""
    if a.yarn is None or not a.yarn[5]:
        return 1.0
    return mscale(a.yarn[0], a.yarn[5]) ** 2


def _rope(x, a):
    """Rotary on x [T, ..., d] at positions 0..T-1, dim i paired with
    dim i + d/2."""
    T, d = x.shape[0], x.shape[-1]
    shape = (T,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = (t.reshape(shape) for t in rope_tables(T, d, a))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- latent attention: the branch, no add --------------------------------------

def _latents(w, xn, a, mode):
    cq = _rms(_mm(xn, w["q_a_proj"], mode), w["q_a_layernorm.weight"], a.eps)
    down = _mm(xn, w["kv_a_proj"], mode)
    ckv = _rms(down[:, :a.rkv], w["kv_a_layernorm.weight"], a.eps)
    return cq, ckv, _rope(down[:, a.rkv:], a)


def _group_qkv(cq, ckv, kr, wg, a, mode):
    """A group's q [T, hg, d_n + d_r] (rope applied, times the YaRN factor
    of the scores: `glm._attend` divides by sqrt(d_n + d_r) alone), k
    likewise (k^R repeated), v [T, hg, d_v]."""
    T = cq.shape[0]
    q = _mm(cq, wg["uq"], mode).reshape(T, -1, a.dn + a.dr)
    q = jnp.concatenate([q[..., :a.dn], _rope(q[..., a.dn:], a)], -1)
    kv = _mm(ckv, wg["ukv"], mode).reshape(T, -1, a.dn + a.dv)
    hg = kv.shape[1]
    key = jnp.concatenate(
        [kv[..., :a.dn], jnp.broadcast_to(kr[:, None], (T, hg, a.dr))], -1)
    return q * softmax_factor(a), key, kv[..., a.dn:]


def attention(w, xn, a, mode=None):
    """Attn(xn) [T, H] of one sequence, one group of heads at a time."""
    lat = _latents(w, xn, a, mode)

    def group(lat, wg):
        q, key, v = _group_qkv(*lat, wg, a, mode)
        o = glm._attend(q, key, v)
        return _mm(o.reshape(o.shape[0], -1), wg["o"], mode)

    out, _ = jax.lax.scan(
        lambda acc, wg: (acc + jax.checkpoint(group)(lat, wg), None),
        jnp.zeros((xn.shape[0], w["o_proj"].shape[1]), F32),
        glm._grouped(w, a))
    return out


# -- the residual path ---------------------------------------------------------

def sinkhorn(M, iters, eps):
    for _ in range(iters):
        M = M / (jnp.sum(M, -1, keepdims=True) + eps)
        M = M / (jnp.sum(M, -2, keepdims=True) + eps)
    return M


def hc_maps(w, X, a, mode=None):
    """(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n]) of X
    [B, n, T, C]; w = {"phi", "scale", "bias"}."""
    n = a.streams
    xf = jnp.concatenate([X[:, j] for j in range(n)], -1)     # [B, T, nC]
    r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + a.eps)
    m = r * _mm(xf, w["phi"], mode)
    s, b = w["scale"], w["bias"]
    h_pre = jax.nn.sigmoid(s[0] * m[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(s[1] * m[..., n:2 * n] + b[n:2 * n])
    raw = (s[2] * m[..., 2 * n:] + b[2 * n:]).reshape(m.shape[:-1] + (n, n))
    return h_pre, h_post, sinkhorn(
        jnp.exp(jnp.clip(raw, a.clamp[0], a.clamp[1])), a.hc_iters, a.hc_eps)


def sum_errors(h_res):
    """[largest |row sum - 1|, largest |column sum - 1|] of H_res."""
    return jnp.stack([jnp.max(jnp.abs(jnp.sum(h_res, -1) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(h_res, -2) - 1.0))])


def hc_half(w, X, F, a, mode=None):
    """(X' [B, n, T, C], what F returns besides y, the maps' `sum_errors`):
    the half-layer around the branch F: u [B, T, C] -> (y, aux)."""
    n = a.streams
    h_pre, h_post, h_res = hc_maps(w, X, a, mode)
    u = sum(h_pre[..., j, None] * X[:, j] for j in range(n))
    y, aux = F(u)
    out = jnp.stack([
        sum(h_res[..., i, j, None] * X[:, j] for j in range(n))
        + h_post[..., i, None] * y for i in range(n)], axis=1)
    return out, aux, sum_errors(h_res)


def expand(x, a):
    return jnp.broadcast_to(x[:, None], (x.shape[0], a.streams)
                            + x.shape[1:])


def reduce(X):
    return jnp.sum(X, axis=1)


# -- the half-layers, a layer, the whole ----------------------------------------

def mixer_half(w, X, a, mode=None):
    """(X', errors) of the attention half on X [B, n, T, C]."""
    m = _part(w, "mixer.")

    def F(u):
        return jax.lax.map(lambda ur: attention(
            m, _rms(ur, w["ln1"], a.eps), a, mode), u), None

    out, _, err = hc_half(_part(w, "hc1."), X, F, a, mode)
    return out, err


def moe_branch(w, u, a, held, mode=None, cap=None, shared=True):
    """(y [B, T, C], rows sent to each held expert [B, n]): the expert
    half's branch alone, FFN(RMSNorm_post(u))."""
    return jax.lax.map(lambda ur: glm._moe(
        _part(w, "mlp."), _rms(ur, w["ln2"], a.eps), a, held, mode, cap,
        shared), u)


def expert_half(w, X, a, held, mode=None, cap=None):
    """(X', (rows sent to each held expert [B, n], errors))."""
    out, sent, err = hc_half(
        _part(w, "hc2."), X,
        lambda u: moe_branch(w, u, a, held, mode, cap), a, mode)
    return out, (sent, err)


def dense_half(w, X, a, mode=None):
    m = _part(w, "mlp.")

    def F(u):
        return _swiglu(_rms(u, w["ln2"], a.eps), m["gate_up_proj"],
                       m["down_proj"], mode), None

    out, _, err = hc_half(_part(w, "hc2."), X, F, a, mode)
    return out, err


def layer_names(a, i):
    """{key the equations use: state-dict name} of layer i, or of the
    prediction module's expert layer (i = `MTP` or the layer count)."""
    mtp = i == MTP or i == a.layers
    base = "mtp.block." if mtp else f"model.layers.{i}."
    names = {"ln1": base + "input_layernorm.weight",
             "ln2": base + "post_attention_layernorm.weight"}
    names.update({"mixer." + k: base + "self_attn." + k for k in glm._MLA})
    names.update({"mlp." + k: base + "mlp." + k for k in (
        glm._MOE if mtp or i >= a.first_dense else glm._DENSE)})
    names.update({"hc1." + k: base + "attn_hc." + k for k in _HC})
    names.update({"hc2." + k: base + "mlp_hc." + k for k in _HC})
    return names


def entry_path_leaves(a, blocks):
    """State-dict names of the hyper-connection leaves of the half-layers
    whose input is an expansion (every stream a copy): the first
    half-layer of the trunk and of the prediction module."""
    first = [i for i in blocks if i == MTP or i == 0]
    return {layer_names(a, i)["hc1." + k] for i in first for k in _HC}


def _halves(names):
    """(the mixer half's keys, the feed-forward half's) of one layer."""
    return ([k for k in names if k == "ln1"
             or k.startswith(("mixer.", "hc1."))],
            [k for k in names if k == "ln2"
             or k.startswith(("mlp.", "hc2."))])


def layer(w, X, i, a, held, mode=None, cap=None):
    """Layer i (or the module's, i = `MTP`) on X [B, n, T, C]: (X', rows
    sent to each held expert or None, the two halves' errors [2, 2])."""
    h, e1 = mixer_half(w, X, a, mode)
    if i != MTP and i < a.first_dense:
        y, e2 = dense_half(w, h, a, mode)
        return y, None, jnp.stack([e1, e2])
    y, (sent, e2) = expert_half(w, h, a, held, mode, cap)
    return y, sent, jnp.stack([e1, e2])


def _layer_w(state, a, i):
    return _up({k: state[n] for k, n in layer_names(a, i).items()})


def hidden_states(state, ids, cfg, held, mode=None):
    """Embedding, expansion, every layer, the sum: (x [B, T, H] before the
    last norm, rows sent per expert layer, the halves' errors)."""
    a = arch(cfg)
    X = expand(jnp.take(state["model.embed_tokens"].astype(F32), ids,
                        axis=0), a)
    sent, errs = [], []
    for i in range(a.layers):
        X, s, e = layer(_layer_w(state, a, i), X, i, a, held, mode)
        errs.append(e)
        if s is not None:
            sent.append(s)
    return reduce(X), sent, errs


def logits(state, ids, cfg, held, mode=None):
    a = arch(cfg)
    x = hidden_states(state, ids, cfg, held, mode)[0]
    return _mm(_rms(x, state["model.norm.weight"].astype(F32), a.eps),
               state["lm_head"].astype(F32), mode)


def module_states(state, x, ids, cfg, held, mode=None):
    """g [B, T, H]: the prediction module's n-stream expert layer over the
    expanded u, reduced, before its final norm."""
    a = arch(cfg)
    u = join(_up({k: state[n] for k, n in _JOIN.items()}),
             state["model.embed_tokens"].astype(F32), x, ids, a.eps, mode)
    return reduce(layer(_layer_w(state, a, MTP), expand(u, a), MTP, a, held,
                        mode)[0])


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
    """(loss, {name: gradient}) of the whole model by autodiff of the whole
    (small sizes). `which`: "total", "main", "mtp" (unweighted) or "all":
    the three as {which: (loss, gradients)} from one forward pass."""
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
    _, vjp, _ = jax.vjp(lambda w_, x_: mixer_half(w_, x_, a, mode), _up(w),
                        x, has_aux=True)
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
    _, vjp, _ = jax.vjp(lambda w_, h_: dense_half(w_, h_, a, mode), _up(w),
                        h, has_aux=True)
    return vjp(dy)


def precompile(shapes, cfg_json, batch, seq, mode=None):
    """Compile the programs `train_steps` will run on [batch, seq] tokens
    from `shapes` alone, into JAX's persistent compilation cache and into
    the GLM reference's `_AOT`, where `_run` finds them: the three kinds of
    half forward and VJP on [batch, n, seq, H], the module's join and the
    head + loss on [batch, seq, H] (the GLM reference's own programs)."""
    a, held = arch(cfg_json), held_of(cfg_json)
    cap = expert_cap(a, held, seq)
    x = jax.ShapeDtypeStruct((batch, seq, a.hidden), F32)
    X = jax.ShapeDtypeStruct((batch, a.streams, seq, a.hidden), F32)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    jobs = [(glm._head_loss, dict(eps=a.eps, mode=mode),
             shapes["model.norm.weight"], shapes["lm_head"], x, ids)]
    module = bool(cfg_json.get("num_nextn_predict_layers", 0))
    if module:
        w = {k: shapes[n] for k, n in _JOIN.items()}
        st = dict(eps=a.eps, mode=mode)
        table = shapes["model.embed_tokens"]
        jobs += [(glm._join_fwd, st, w, table, x, ids),
                 (glm._join_bwd, st, w, table, x, ids, x)]

    def halves(i):
        names = layer_names(a, i)
        return ({k: shapes[names[k]] for k in part}
                for part in _halves(names))

    mixer, ffn = halves(MTP if module else a.layers - 1)
    st = dict(kind=CAUSAL, a=a, mode=mode)
    jobs += [(_mixer_fwd, st, mixer, X), (_mixer_bwd, st, mixer, X, X)]
    if module or a.first_dense < a.layers:
        st = dict(a=a, held=held, mode=mode, cap=cap)
        jobs += [(_expert_fwd, st, ffn, X), (_expert_bwd, st, ffn, X, X)]
    if a.first_dense:
        st, (_, ffn) = dict(a=a, mode=mode), halves(0)
        jobs += [(_dense_fwd, st, ffn, X), (_dense_bwd, st, ffn, X, X)]

    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        list(pool.map(lambda job: _build(*job), jobs))


def train_steps(make_state, batches, cfg_json, trainer, mode=None,
                keep=np.asarray):
    """Follow `len(batches)` training steps from the state `make_state()`
    returns, as `reference_glm4_moe_lite.train_steps` does (stored dtypes,
    float32 arithmetic, HALF a layer at a time, each half's n-stream input
    kept on the HOST), with the residual path's expansion behind the
    embedding and behind the module's join and its sum in front of both
    heads. `trainer` may carry `mtp_loss_weight` and `hc_sinkhorn_iters`
    (faults of `correct.faults`: the module's loss left out; Sinkhorn cut
    short). Returns what the GLM reference returns, `delta_norms` without
    the `entry_path_leaves` (the module docstring says why), and
    "hc_res_sum_err": a step's [largest |row sum - 1|, largest |column sum
    - 1|] over its half-layers."""
    a, held = arch(cfg_json), held_of(cfg_json)
    a = a._replace(hc_iters=int(trainer.get("hc_sinkhorn_iters",
                                            a.hc_iters)))
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
    sum_errs = []
    mix_st = dict(kind=CAUSAL, a=a, mode=mode)
    head_st = dict(eps=a.eps, mode=mode)

    def half(i, which):
        return {k: p[names[i][k]] for k in _halves(names[i])[which]}

    def update(name, g, t):
        m, v = mom.pop(name, None) or (jnp.zeros_like(p[name]),
                                       jnp.zeros_like(p[name]))
        p[name], m, v, gn = _adamw(p[name], m, v, g, lr, np.float32(t), hp)
        mom[name] = (m, v)
        if t == 1:
            grad_norms[name] = gn

    def forward(i, x, xs, moe_st, errs):
        """Block i on X, both halves' inputs kept on `xs`."""
        nonlocal most
        xs.append(keep(x))
        x, e = _run(_mixer_fwd, mix_st, half(i, 0), x)
        errs.append(e)
        xs.append(keep(x))
        if i != MTP and i < a.first_dense:
            x, e = _run(_dense_fwd, dict(a=a, mode=mode), half(i, 1), x)
            errs.append(e)
            return x
        x, (sent, e) = _run(_expert_fwd, moe_st, half(i, 1), x)
        errs.append(e)
        most = max(most, int(jnp.max(sent)))
        if most > moe_st["cap"]:
            raise AssertionError(
                f"reference: an expert of block {i} was sent {most} rows, "
                f"more than the {moe_st['cap']} it multiplies")
        return x

    def backward(i, dx, xs, moe_st, t):
        """Block i's VJP under dX; its leaves are updated here."""
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
            X = expand(_embed(p["model.embed_tokens"], ids), a)
            xs, errs = [], []
            for i in range(a.layers):
                X = forward(i, X, xs, moe_st, errs)
            x = reduce(X)
            del X
            main, (dn, dhead, dx) = _run(
                glm._head_loss, head_st, p["model.norm.weight"],
                p["lm_head"], x, targets(ids, 1))
            update("model.norm.weight", dn, t)
            extra, dtable = jnp.zeros((), F32), None
            if module:
                jw = {k: p[n] for k, n in _JOIN.items()}
                ys = []
                u = _run(glm._join_fwd, head_st, jw, p["model.embed_tokens"],
                         x, ids)
                x_last = keep(x)
                del x
                g = reduce(forward(MTP, expand(u, a), ys, moe_st, errs))
                del u
                extra, (dn, dh2, dg) = _run(
                    glm._head_loss, head_st, p["mtp.norm.weight"],
                    p["lm_head"], g, targets(ids, 2))
                del g
                update("mtp.norm.weight", lam * dn, t)
                dhead = dhead + lam * dh2
                du = reduce(backward(MTP, expand(lam * dg, a), ys, moe_st,
                                     t))
                dw, dtable, dx2 = _run(
                    glm._join_bwd, head_st, jw, p["model.embed_tokens"],
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
            sum_errs.append(jnp.max(jnp.stack(errs), axis=0))
            dX = expand(dx, a)
            for i in reversed(range(a.layers)):
                dX = backward(i, dX, xs, moe_st, t)
            g_table = _embed_grad(p["model.embed_tokens"], ids, reduce(dX))
            update("model.embed_tokens",
                   g_table if dtable is None else g_table + dtable, t)
        del mom, xs, dx, dX, dn, dhead, g_table, dtable
        start = make_state()
        delta = {k: _diff_norm(p[k], start[k])
                 for k in sorted(trained - entry_path_leaves(a, blocks))}
    out = {"losses": [float(x) for x in losses],
           "main_losses": [float(x) for x in mains],
           "mtp_losses": [float(x) for x in extras],
           "grad_norms": {k: float(v) for k, v in grad_norms.items()},
           "delta_norms": {k: float(v) for k, v in delta.items()},
           "expert_rows": most,
           "hc_res_sum_err": [[float(v) for v in e] for e in sum_errs]}
    print(f"reference: hc_res_sum_err by step {out['hc_res_sum_err']} "
          f"(Sinkhorn iterations {a.hc_iters})", flush=True)
    return out
