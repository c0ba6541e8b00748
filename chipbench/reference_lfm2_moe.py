"""The plain reference of the lfm2_moe language model (LFM2-8B-A1B): a
pre-norm decoder whose mixers are of two kinds in a fixed pattern
(`layer_types`: gated short convolutions, and grouped-query attention whose
q and k are normalised a head before rotary), whose feed-forward is a dense
SwiGLU in the first `num_dense_layers` layers and a sigmoid-routed mixture
of experts with NO shared expert after, and whose head is the embedding
table. Written from the equations in `jax.numpy` float32 at matmul
precision "highest": no kernel, nothing imported from the program (the
control's rounding and the optimizer are `chipbench/reference.py`'s). The
CPU tests hold `paddle_tpu/models/lfm2_moe.py` to this file, and
`train_steps` below decides the benchmark cell's `correct`.

Equations (x the layer input, one sequence [T, H], eps `norm_eps`):
  block   h = x + Op(RMSNorm(x; operator_norm));
          y = h + FF(RMSNorm(h; ffn_norm))
  conv    [B | C | X] = x W_in  (W_in [H, 3H], no bias);  u = B * X;
          v_t = sum_{j < L} w_j * u_{t - (L-1) + j}  (w [L, H], L =
          `conv_L_cache` = 3 taps; u = 0 before the sequence's first
          token; no bias, NO activation);  y = C * v;  Op = y W_out
  GQA     q = x W_q [nh x d], k, v = x W_k, x W_v [kvh x d], no bias;
          q <- RMSNorm_d(q; w_qn), k <- RMSNorm_d(k; w_kn)  (over a head's d
          channels, eps, one [d] weight each);  q, k <- RoPE_theta over all d
          dims (rotate-half pairing);  o = causal softmax(q k^T / sqrt(d)) v,
          nh / kvh query heads a key-value head;  Op = o W_o
  dense   (silu(a W_1) * (a W_3)) W_2, width `intermediate_size`
  MoE     s = sigmoid(a W_r) over ALL experts; chosen = top-k of s + b (b the
          `expert_bias`, a buffer: it selects only);
          g = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor;
          FF = sum_k g_k E_k(a), E a SwiGLU of width `moe_intermediate_size`.
          Nothing is added beside the routed experts.
  head    logits = RMSNorm(h^L; model.norm) E^T  (E the embedding table)
  loss    mean_{i <= T-2} CE(logits_i, t_{i+1})
`held = (e0, n)`: the experts [e0, e0 + n) live here, the router keeps
every output and its top-k, and a pair routed to an absent expert adds
nothing. held = (0, num_experts) is the uncut layer.

Departures from the published block (each `assumed` in the configuration's
file where the catalog row does not fix it):
  * the order B | C | X of the projection's columns, the place of the norm
    of q and k (before rotary), the 1e-6 and the tied head are the family's
    public code as remembered, not fetched;
  * rotary tables are made on the host from float64 angles;
  * because plain f32 at the benchmark's sizes would not fit one chip, none
    changing a value: attention runs one key-value head's group and one
    block of query rows at a time, the dense feed-forward, the head and the
    loss a block of rows at a time, backward passes recompute inside blocks,
    an expert multiplies only the (at most `cap`) rows routed to it, and
    `train_steps` goes half a layer at a time.

State-dict layout (matrices [in, out]): `conv.in_proj` holds the B | C | X
columns, `conv.conv_weight` is [3, H] with tap j on u_{t-2+j}, `qkv_proj`
the q | k | v columns, `*gate_up*` gate | up columns, expert stacks are
[n_held, ...], `model.embed_tokens` [vocab rows, H] is also the head.

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
CONV, ATTENTION = "conv", "full_attention"
NORM_TOPK_EPS = 1e-6
ROWS = 256                 # query rows of one attention block


class Arch(NamedTuple):
    hidden: int
    eps: float
    layer_types: tuple
    first_dense: int
    nh: int
    kvh: int
    d: int
    theta: float
    taps: int
    m: int             # expert width
    n_routed: int
    top_k: int
    norm_topk: bool
    scaling: float


def arch(cfg):
    """The static sizes the equations need, from a configuration file."""
    nh = cfg["num_attention_heads"]
    return Arch(
        hidden=cfg["hidden_size"], eps=float(cfg["norm_eps"]),
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        first_dense=cfg["num_dense_layers"], nh=nh,
        kvh=cfg["num_key_value_heads"], d=cfg["hidden_size"] // nh,
        theta=float(cfg["rope_theta"]), taps=cfg["conv_L_cache"],
        m=cfg["moe_intermediate_size"],
        n_routed=cfg.get("reduced_from", {}).get("num_experts",
                                                 cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]))


def held_of(cfg):
    """(first expert, experts) this configuration holds of each layer."""
    return cfg.get("expert_offset", 0), cfg["num_experts"]


def layer_kind(a, i):
    return a.layer_types[i]


def _mm(x, w, mode=None):
    return jnp.matmul(_fake_quant(x, mode, -1), _fake_quant(w, mode, 0),
                      precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, w_gate_up, w_down, mode):
    gu = _mm(x, w_gate_up, mode)
    m = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :m]) * gu[..., m:], w_down, mode)


def _by_rows(fn, x, block):
    """fn over x [T, ...] a block of rows at a time (rows are independent),
    a block's intermediates recomputed in the backward."""
    T = x.shape[0]
    if T % block:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape((T // block, block) + x.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


# -- the gated short convolution ----------------------------------------------

def gated_conv(bcx, w):
    """C * conv(B * X): bcx [T, 3H] (B | C | X), w [taps, H]; tap j
    multiplies u_{t - (taps-1) + j}, zeros before t = 0."""
    taps, H = w.shape
    T = bcx.shape[0]
    u = jnp.pad(bcx[:, :H] * bcx[:, 2 * H:], ((taps - 1, 0), (0, 0)))
    v = sum(u[j:j + T] * w[j] for j in range(taps))
    return bcx[:, H:2 * H] * v


def _conv_mixer(w, xn, a, mode):
    y = gated_conv(_mm(xn, w["in_proj"], mode), w["conv_weight"])
    return _mm(y, w["out_proj"], mode)


# -- grouped-query attention with a head's own norm ---------------------------

def _rope_tables(T, d, theta):
    """cos and sin of t * theta^(-2i/d), [T, d/2] float32, made on the
    host in float64 (the program's tables are made the same way)."""
    freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(T, dtype=np.float64)[:, None] * freq[None]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rope(x, theta):
    """Rotary on x [T, heads, d] at positions 0..T-1: dim i pairs with dim
    i + d/2, angle t * theta^(-2i/d)."""
    T, d = x.shape[0], x.shape[-1]
    cos, sin = (t[:, None, :] for t in _rope_tables(T, d, theta))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v):
    """o [T, g, d] of one key-value head's g query heads: causal softmax
    attention, a block of query rows at a time."""
    T, g, d = q.shape
    block = T if T % ROWS else ROWS

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        t = i * block + jnp.arange(block)
        ok = jnp.arange(T)[None] <= t[:, None]
        s = jnp.einsum("tgd,sd->gts", qs, k, precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(ok[None], s, NEG), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, v, precision=HI)

    out = jax.lax.map(jax.checkpoint(rows), jnp.arange(T // block))
    return out.reshape(T, g, d)


def _gqa_mixer(w, xn, a, mode):
    """Attention output [T, H], one key-value head's group at a time."""
    nh, kvh, d, H = a.nh, a.kvh, a.d, a.hidden
    g = nh // kvh
    qkv = w["qkv_proj"]
    grouped = {
        "q": qkv[:, :nh * d].reshape(H, kvh, g * d).transpose(1, 0, 2),
        "k": qkv[:, nh * d:(nh + kvh) * d].reshape(H, kvh, d).transpose(
            1, 0, 2),
        "v": qkv[:, (nh + kvh) * d:].reshape(H, kvh, d).transpose(1, 0, 2),
        "o": w["out_proj"].reshape(kvh, g * d, H),
    }
    qn, kn = w["q_layernorm.weight"], w["k_layernorm.weight"]

    def group(xn, wg):
        q = _rms(_mm(xn, wg["q"], mode).reshape(-1, g, d), qn, a.eps)
        k = _rms(_mm(xn, wg["k"], mode)[:, None], kn, a.eps)
        o = _attend(_rope(q, a.theta), _rope(k, a.theta)[:, 0],
                    _mm(xn, wg["v"], mode))
        return _mm(o.reshape(-1, g * d), wg["o"], mode)

    def body(acc, wg):
        return acc + jax.checkpoint(group)(xn, wg), None

    return jax.lax.scan(body, jnp.zeros(xn.shape, F32), grouped)[0]


# -- the feed-forward halves ---------------------------------------------------

def route(xn, w_router, bias, a):
    """(expert ids [T, k], weights [T, k]) over all the router's outputs:
    the top-k of score + bias, weighted by the scores alone over their sum
    + 1e-6."""
    s = jax.nn.sigmoid(jnp.matmul(xn, w_router, precision=HI))
    top_i = jax.lax.top_k(s + jax.lax.stop_gradient(bias), a.top_k)[1]
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if a.norm_topk:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + NORM_TOPK_EPS)
    return top_i, top_s * a.scaling


def _moe(w, xn, a, held, mode, cap):
    """The held experts' part, and nothing beside it. Returns (y, rows
    sent to each held expert [n])."""
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

    return jax.lax.scan(
        body, jnp.zeros_like(xn),
        (e0 + jnp.arange(n), {"gu": w["experts_gate_up"],
                              "down": w["experts_down"]}))


# -- a layer, the head, the whole ----------------------------------------------

_CONV = ("in_proj", "conv_weight", "out_proj")
_GQA = ("qkv_proj", "q_layernorm.weight", "k_layernorm.weight", "out_proj")
_MOE = ("router", "experts_gate_up", "experts_down",
        "e_score_correction_bias")
_DENSE = ("gate_up_proj", "down_proj")
BUFFERS = ("e_score_correction_bias",)       # in the state, never trained


def layer_names(a, i):
    """{key the equations use: state-dict name} of layer i."""
    base = f"model.layers.{i}."
    mixer, leaves = (("self_attn.", _GQA) if layer_kind(a, i) == ATTENTION
                     else ("conv.", _CONV))
    names = {"ln1": base + "operator_norm.weight",
             "ln2": base + "ffn_norm.weight"}
    names.update({"mixer." + k: base + mixer + k for k in leaves})
    names.update({"mlp." + k: base + "mlp." + k
                  for k in (_MOE if i >= a.first_dense else _DENSE)})
    return names


def _part(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def mixer_half(w, x, kind, a, mode=None):
    """h = x + Op(RMSNorm(x)) on x [B, T, H] float32, a sequence at a
    time."""
    mixer = _gqa_mixer if kind == ATTENTION else _conv_mixer
    return jax.lax.map(
        lambda xr: xr + mixer(_part(w, "mixer."), _rms(xr, w["ln1"], a.eps),
                              a, mode), x)


def expert_half(w, h, a, held, mode=None, cap=None):
    """(h + MoE(RMSNorm(h)), rows sent to each held expert [B, n])."""
    def one(hr):
        y, sent = _moe(_part(w, "mlp."), _rms(hr, w["ln2"], a.eps), a, held,
                       mode, cap)
        return hr + y, sent

    return jax.lax.map(one, h)


def dense_half(w, h, a, mode=None, block=4096):
    """h + SwiGLU(RMSNorm(h)) of a leading dense layer, a block of rows at
    a time."""
    m = _part(w, "mlp.")

    def rows(hb):
        return hb + _swiglu(_rms(hb, w["ln2"], a.eps), m["gate_up_proj"],
                            m["down_proj"], mode)

    H = h.shape[-1]
    return _by_rows(rows, h.reshape(-1, H), block).reshape(h.shape)


def layer(w, x, i, a, held, mode=None, cap=None):
    """Layer i on x [B, T, H] float32: (y, rows sent to each held expert
    [B, n] or None)."""
    h = mixer_half(w, x, layer_kind(a, i), a, mode)
    if i < a.first_dense:
        return dense_half(w, h, a, mode), None
    return expert_half(w, h, a, held, mode, cap)


def head_loss(norm_w, table, x, labels, eps, mode=None, block=1024):
    """Mean next-token cross-entropy over x [B, T, H], labels [B, T], the
    head the embedding table [V, H]: a block of rows at a time over all
    B * T rows, a sequence's last row masked (it has no label)."""
    B, T, H = x.shape
    xr = _rms(x, norm_w, eps).reshape(-1, H)
    tgt = jnp.concatenate(
        [labels[:, 1:], jnp.full((B, 1), -1, labels.dtype)], 1).reshape(-1)
    if (B * T) % block:
        block = B * T

    def rows(args):
        xb, tb = args
        lg = _mm(xb, table.T, mode)
        lse = jax.nn.logsumexp(lg, axis=-1)
        own = jnp.take_along_axis(lg, jnp.maximum(tb, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(tb >= 0, lse - own, 0.0))

    parts = jax.lax.map(jax.checkpoint(rows),
                        (xr.reshape(-1, block, H), tgt.reshape(-1, block)))
    return jnp.sum(parts) / (B * (T - 1))


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
    for i in range(len(a.layer_types)):
        x, s = layer(_layer_w(state, a, i), x, i, a, held, mode)
        if s is not None:
            sent.append(s)
    return x, sent


def logits(state, ids, cfg, held, mode=None):
    a = arch(cfg)
    x = hidden_states(state, ids, cfg, held, mode)[0]
    return _mm(_rms(x, state["model.norm.weight"].astype(F32), a.eps),
               state["model.embed_tokens"].astype(F32).T, mode)


def loss(state, ids, cfg, held, mode=None):
    x = hidden_states(state, ids, cfg, held, mode)[0]
    return head_loss(state["model.norm.weight"].astype(F32),
                     state["model.embed_tokens"].astype(F32), x, ids,
                     arch(cfg).eps, mode)


def loss_and_grads(state, ids, cfg, held):
    """(loss, {name: gradient}) of the whole model, by autodiff of the
    whole (small sizes: nothing is freed between layers). The table's
    gradient is the sum of its two uses."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda s: loss(s, ids, cfg, held)))(_up(state))


# -- the benchmark's own: training steps, half a layer at a time --------------

@functools.partial(jax.jit, static_argnames=("kind", "a", "mode"))
def _mixer_fwd(w, x, kind, a, mode):
    return mixer_half(_up(w), x, kind, a, mode)


@functools.partial(jax.jit, static_argnames=("kind", "a", "mode"))
def _mixer_bwd(w, x, dh, kind, a, mode):
    _, vjp = jax.vjp(lambda w_, x_: mixer_half(w_, x_, kind, a, mode),
                     _up(w), x)
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
def _head_loss(norm_w, table, x, labels, eps, mode):
    return jax.value_and_grad(
        lambda nw, tb, x_: head_loss(nw, tb, x_, labels, eps, mode),
        argnums=(0, 1, 2))(norm_w.astype(F32), table.astype(F32), x)


@jax.jit
def _tied_grad(table, ids, dx, d_head):
    """The table's gradient: the head's plus the lookup's."""
    return d_head + _embed_grad(table, ids, dx)


def expert_cap(a, held, tokens):
    """Rows an expert may be sent before `train_steps` refuses to go on:
    eight times a uniform router's share, never under 256 (as
    `reference_glm4_moe_lite.py`: a seeded router sends one expert four
    times the share)."""
    return min(tokens, max(256, 8 * -(-tokens * a.top_k // a.n_routed)))


def _halves(names):
    """(the mixer half's keys, the feed-forward half's) of one layer."""
    return ([k for k in names if k == "ln1" or k.startswith("mixer.")],
            [k for k in names if k == "ln2" or k.startswith("mlp.")])


def precompile(shapes, cfg_json, batch, seq, mode=None):
    """Compile the programs `train_steps` will run on [batch, seq] tokens
    from `shapes` ({name: ShapeDtypeStruct} of the state) alone: each kind
    of mixer half, the dense half and the expert half forward and VJP, the
    head + loss, into JAX's persistent compilation cache, where
    `train_steps`' own calls find them. Nothing runs and nothing is held
    on the device, so a driver can do it on another thread while its
    program compiles."""
    a, held = arch(cfg_json), held_of(cfg_json)
    cap = expert_cap(a, held, seq)
    x = jax.ShapeDtypeStruct((batch, seq, a.hidden), F32)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    jobs = [functools.partial(
        _head_loss.lower, shapes["model.norm.weight"],
        shapes["model.embed_tokens"], x, ids, a.eps, mode)]
    seen = set()
    for i, kind in enumerate(a.layer_types):
        names = layer_names(a, i)
        mixer, ffn = ({k: shapes[names[k]] for k in part}
                      for part in _halves(names))
        if kind not in seen:
            jobs += [functools.partial(_mixer_fwd.lower, mixer, x, kind, a,
                                       mode),
                     functools.partial(_mixer_bwd.lower, mixer, x, x, kind,
                                       a, mode)]
        ffn_kind = "dense" if i < a.first_dense else "experts"
        if ffn_kind not in seen and ffn_kind == "dense":
            jobs += [functools.partial(_dense_fwd.lower, ffn, x, a, mode),
                     functools.partial(_dense_bwd.lower, ffn, x, x, a, mode)]
        elif ffn_kind not in seen:
            jobs += [functools.partial(_expert_fwd.lower, ffn, x, a, held,
                                       mode, cap),
                     functools.partial(_expert_bwd.lower, ffn, x, x, a, held,
                                       mode, cap)]
        seen.update((kind, ffn_kind))

    def build(lower):
        with jax.default_matmul_precision("highest"):
            lower().compile()

    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        list(pool.map(build, jobs))


def train_steps(make_state, batches, cfg_json, trainer, mode=None,
                keep=np.asarray):
    """Follow `len(batches)` training steps from the state `make_state()`
    returns: parameters and AdamW moments stored in the dtype the
    configuration trains in, all arithmetic float32, HALF a layer at a
    time, each half's input kept for the backward on the HOST (`keep`).
    The embedding table's gradient is the head's plus the lookup's,
    applied once. Returns {"losses", "grad_norms", "delta_norms",
    "expert_rows" (the most rows any held expert was sent)}."""
    a, held = arch(cfg_json), held_of(cfg_json)
    n_layers = len(a.layer_types)
    hp = (float(trainer["beta1"]), float(trainer["beta2"]),
          float(trainer["epsilon"]), float(trainer["weight_decay"]))
    lr = np.float32(trainer["learning_rate"])
    p = make_state()
    names = [layer_names(a, i) for i in range(n_layers)]
    trained = {"model.embed_tokens", "model.norm.weight"}
    trained.update(n for per in names for n in per.values()
                   if not n.endswith(BUFFERS))
    mom, losses, grad_norms, most = {}, [], {}, 0

    def half(i, which):
        """The weights one half of layer i reads: its norm and its part."""
        return {k: p[names[i][k]] for k in _halves(names[i])[which]}

    def update(name, g, t):
        m, v = mom.pop(name, None) or (jnp.zeros_like(p[name]),
                                       jnp.zeros_like(p[name]))
        p[name], m, v, gn = _adamw(p[name], m, v, g, lr, np.float32(t), hp)
        mom[name] = (m, v)
        if t == 1:
            grad_norms[name] = gn

    with jax.default_matmul_precision("highest"):
        for t, ids in enumerate(jnp.asarray(batches), start=1):
            cap = expert_cap(a, held, ids.shape[1])
            x = _embed(p["model.embed_tokens"], ids)
            xs = []
            for i in range(n_layers):
                xs.append(keep(x))
                x = _mixer_fwd(half(i, 0), x, layer_kind(a, i), a, mode)
                xs.append(keep(x))
                if i < a.first_dense:
                    x = _dense_fwd(half(i, 1), x, a, mode)
                    continue
                x, sent = _expert_fwd(half(i, 1), x, a, held, mode, cap)
                most = max(most, int(jnp.max(sent)))
                if most > cap:
                    raise AssertionError(
                        f"reference: an expert of layer {i} was sent {most} "
                        f"rows, more than the {cap} it multiplies")
            loss, (dn, d_head, dx) = _head_loss(
                p["model.norm.weight"], p["model.embed_tokens"], x, ids,
                a.eps, mode)
            del x
            losses.append(loss)
            update("model.norm.weight", dn, t)
            for i in reversed(range(n_layers)):
                h_in = jnp.asarray(xs.pop())
                if i < a.first_dense:
                    dw, dx = _dense_bwd(half(i, 1), h_in, dx, a, mode)
                else:
                    dw, dx = _expert_bwd(half(i, 1), h_in, dx, a, held, mode,
                                         cap)
                for k, g in dw.items():
                    if not names[i][k].endswith(BUFFERS):
                        update(names[i][k], g, t)
                dw, dx = _mixer_bwd(half(i, 0), jnp.asarray(xs.pop()), dx,
                                    layer_kind(a, i), a, mode)
                for k, g in dw.items():
                    update(names[i][k], g, t)
            update("model.embed_tokens",
                   _tied_grad(p["model.embed_tokens"], ids, dx, d_head), t)
        del mom, xs, dx, dw, dn, d_head, h_in
        start = make_state()
        delta = {k: _diff_norm(p[k], start[k]) for k in sorted(trained)}
    return {"losses": [float(x) for x in losses],
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()},
            "expert_rows": most}
