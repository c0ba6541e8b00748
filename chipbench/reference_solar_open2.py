"""The benchmark's copy of the plain reference of the Solar-Open2 block
(`tests/reference/solar_open2.py`; a test holds the text between the
docstring and `train_steps`' section equal): a pre-norm decoder whose
mixers are of two kinds in a fixed pattern (`gqa_layers`: softmax
grouped-query attention WITHOUT rotary and with a sigmoid output gate;
every other layer a gated delta-rule linear attention, "KDA") and whose
feed-forward is, in every layer, a sigmoid-routed mixture of experts
with one shared expert. Written from the equations in `jax.numpy`
float32 at matmul precision "highest": no kernel, no chunking of the
recurrence (the delta rule runs token by token), nothing imported from
the program.

Equations (x the layer input, one sequence, eps the config's):
  block   h = x + Mixer(RMSNorm(x));  y = h + MoE(RMSNorm(h))
  GQA     q = x Wq [nh x d], k, v = x Wk, x Wv [kvh x d];
          a = causal softmax(q k^T / sqrt(d)) v, no rotary;
          out = (a * sigmoid(x Wg)) Wo
  KDA     per head, d_k = d_v = d: q = L2norm(SiLU(conv4(x Wq))), k
          likewise, v = SiLU(conv4(x Wv)) (causal depthwise conv, 4 taps);
          g_t = -exp(A_log) * softplus(Wup(Wdown x_t) + dt_bias) in R^d,
          alpha_t = exp(g_t); beta_t = 2 sigmoid(w_beta x_t);
          S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1}
                + beta_t k_t v_t^T;   o_t = S_t^T q_t / sqrt(d);
          out = [RMSNorm_head(o_t) * sigmoid(Uup(Udown x_t))] Wo
  MoE     s = sigmoid(x Wr) over ALL experts; top-k by s;
          w = s_top / sum(s_top) * routed_scaling_factor;
          y = Shared(x) + sum_k w_k E_k(x), E and Shared SwiGLU.
`held = (e0, n)`: the experts [e0, e0 + n) live here, the router keeps
every output and its top-k, and a pair routed to an absent expert adds
nothing. held = (0, n_routed) is the uncut layer.

Departures, each because plain f32 at the benchmark's sizes would not
fit one chip, none changing a value: mixers run one group of heads at a
time, attention one block of query rows at a time, the head and loss one
block of rows at a time, the recurrence's backward recomputes inside
blocks of tokens, and an expert multiplies only the (at most `cap`) rows
routed to it: `layer` also returns how many rows it was sent, for the
caller to hold against `cap`.

State-dict layout (matrices [in, out]): `qkv_proj` holds q | k | v
columns, `*_gate_up` gate | up columns, `conv_weight` is [4, channels]
with tap j on x_{t-3+j}, expert stacks are [n_held, ...].

`mode` computes every weight matmul but the router's in a lower
precision (the control of `correct`): "fp8" (e4m3, per-row / per-column
scales), "int8" or "bf16"; the gradient passes straight through.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


class Arch(NamedTuple):
    hidden: int
    nh: int            # softmax layers: query heads
    kvh: int
    d: int
    nl: int            # linear-attention heads
    dl: int
    rank: int          # low rank of the decay and the output gate
    taps: int
    m: int             # expert width
    n_routed: int
    top_k: int
    norm_topk: bool
    scaling: float
    eps: float
    gqa_layers: tuple


def arch(cfg):
    """The static sizes the equations need, from a configuration file."""
    lin = cfg["linear_attn_config"]
    return Arch(
        hidden=cfg["hidden_size"], nh=cfg["num_attention_heads"],
        kvh=cfg["num_key_value_heads"], d=cfg["head_dim"],
        nl=lin["num_heads"], dl=lin["head_dim"],
        rank=cfg.get("kda_low_rank", lin["head_dim"]),
        taps=lin["short_conv_kernel_size"], m=cfg["moe_intermediate_size"],
        n_routed=cfg.get("reduced_from", {}).get("n_routed_experts",
                                                 cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
        gqa_layers=tuple(cfg["gqa_layers"]))


def held_of(cfg):
    """(first expert, experts) this configuration holds of each layer."""
    return cfg.get("expert_offset", 0), cfg["n_routed_experts"]


def vocab_of(cfg):
    """Rows of the vocabulary this configuration holds."""
    return cfg.get("vocab_rows", cfg["vocab_size"])


def layer_kind(a, i):
    return "gqa" if i in a.gqa_layers else "kda"


def _fake_quant(x, mode, axis):
    if mode is None:
        return x
    if mode == "bf16":
        q = x.astype(jnp.bfloat16).astype(F32)
    else:
        top = {"fp8": 448.0, "int8": 127.0}[mode]
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top + 1e-30
        if mode == "fp8":
            q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
        else:
            q = jnp.round(x / s) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, w, mode=None):
    return jnp.matmul(_fake_quant(a, mode, -1), _fake_quant(w, mode, 0),
                      precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, w_gate_up, w_down, mode):
    gu = _mm(x, w_gate_up, mode)
    m = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :m]) * gu[..., m:], w_down, mode)


def _by_group(fn, xn, grouped):
    """sum over groups of fn(xn, group's weights): one group at a time,
    and the backward recomputes a group instead of keeping it."""
    def body(acc, wg):
        return acc + jax.checkpoint(fn)(xn, wg), None
    out, _ = jax.lax.scan(body, jnp.zeros(xn.shape, F32), grouped)
    return out


# -- softmax attention without rotary, gated ---------------------------------

def _attend(q, k, v, block=256):
    """One KV head's group: q [T, g, d], k, v [T, d] -> [T, g, d], a
    block of query rows at a time."""
    T, g, d = q.shape
    if T % block:
        block = T
    cols = jnp.arange(T)

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        s = jnp.einsum("tgd,sd->gts", qs, k, precision=HI) / math.sqrt(d)
        seen = (i * block + jnp.arange(block))[:, None] >= cols[None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, v, precision=HI)

    o = jax.lax.map(jax.checkpoint(rows), jnp.arange(T // block))
    return o.reshape(T, g, d)


def _gqa_mixer(w, xn, a, mode):
    H, nh, kvh, d = a.hidden, a.nh, a.kvh, a.d
    g = nh // kvh
    qkv = w["qkv_proj"]
    grouped = {
        "q": qkv[:, :nh * d].reshape(H, kvh, g * d).transpose(1, 0, 2),
        "k": qkv[:, nh * d:(nh + kvh) * d].reshape(H, kvh, d).transpose(
            1, 0, 2),
        "v": qkv[:, (nh + kvh) * d:].reshape(H, kvh, d).transpose(1, 0, 2),
        "gate": w["gate_proj"].reshape(H, kvh, g * d).transpose(1, 0, 2),
        "o": w["o_proj"].reshape(kvh, g * d, H),
    }

    def group(xn, wg):
        q = _mm(xn, wg["q"], mode).reshape(-1, g, d)
        o = _attend(q, _mm(xn, wg["k"], mode), _mm(xn, wg["v"], mode))
        gate = jax.nn.sigmoid(_mm(xn, wg["gate"], mode))
        return _mm(o.reshape(-1, g * d) * gate, wg["o"], mode)

    return _by_group(group, xn, grouped)


# -- gated delta-rule linear attention ---------------------------------------

def _conv_silu(x, w):
    """Causal depthwise convolution then SiLU: x [T, C], w [taps, C];
    tap j multiplies x_{t - (taps-1) + j}."""
    taps = w.shape[0]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    T = x.shape[0]
    y = sum(xp[j:j + T] * w[j] for j in range(taps))
    return jax.nn.silu(y)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule_recurrence(q, k, v, g, beta, block=128):
    """The gated delta rule, token by token. q, k, g [T, h, dk],
    v [T, h, dv], beta [T, h]; g is the log of the per-channel decay.
    Returns o [T, h, dv] = S_t^T q_t / sqrt(dk). The backward keeps the
    state once a block of tokens and recomputes inside a block."""
    T, h, dk = q.shape
    scale = 1.0 / math.sqrt(dk)

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = S * jnp.exp(g_t)[..., None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=HI))
        S = S + k_t[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HI) * scale

    S0 = jnp.zeros((h, dk, v.shape[-1]), F32)
    xs = (q, k, v, g, beta)
    if T % block:
        return jax.lax.scan(step, S0, xs)[1]
    xs = jax.tree_util.tree_map(
        lambda x: x.reshape((T // block, block) + x.shape[1:]), xs)
    inner = jax.checkpoint(lambda S, blk: jax.lax.scan(step, S, blk))
    o = jax.lax.scan(inner, S0, xs)[1]
    return o.reshape((T,) + o.shape[2:])


def _kda_groups(a):
    """Groups the heads are handled in: 4 heads each where they divide."""
    return a.nl // 4 if a.nl % 4 == 0 else 1


def _kda_mixer(w, xn, a, mode):
    H, nl, dl = a.hidden, a.nl, a.dl
    G = _kda_groups(a)
    hg = nl // G

    def cols(mat, parts=1):
        """[in, parts * nl * dl] -> [G, in, parts, hg * dl]."""
        n_in = mat.shape[0]
        return mat.reshape(n_in, parts, G, hg * dl).transpose(2, 0, 1, 3)

    grouped = {
        "qkv": cols(w["qkv_proj"], 3),
        "conv": cols(w["conv_weight"], 3),
        "decay_up": cols(w["decay_up"])[:, :, 0],
        "dt_bias": w["dt_bias"].reshape(G, hg * dl),
        "A_log": w["A_log"].reshape(G, hg),
        "beta": w["beta_proj"].reshape(H, G, hg).transpose(1, 0, 2),
        "gate_up": cols(w["gate_up"])[:, :, 0],
        "o": w["o_proj"].reshape(G, hg * dl, H),
    }
    low_decay = _mm(xn, w["decay_down"], mode)
    low_gate = _mm(xn, w["gate_down"], mode)
    o_norm = w["o_norm.weight"]

    def group(xn, wg):
        def proj(j):
            return _conv_silu(_mm(xn, wg["qkv"][:, j], mode),
                              wg["conv"][:, j]).reshape(-1, hg, dl)

        q, k, v = _l2norm(proj(0)), _l2norm(proj(1)), proj(2)
        soft = jax.nn.softplus(_mm(low_decay, wg["decay_up"], mode)
                               + wg["dt_bias"])
        g = -jnp.exp(wg["A_log"])[None, :, None] * soft.reshape(-1, hg, dl)
        beta = 2.0 * jax.nn.sigmoid(_mm(xn, wg["beta"], mode))
        o = delta_rule_recurrence(q, k, v, g, beta)
        o = _rms(o, o_norm, a.eps).reshape(-1, hg * dl)
        gate = jax.nn.sigmoid(_mm(low_gate, wg["gate_up"], mode))
        return _mm(o * gate, wg["o"], mode)

    return _by_group(group, xn, grouped)


# -- the mixture of experts ---------------------------------------------------

def route(xn, w_router, a):
    """(expert ids [T, k], weights [T, k]) over all the router's outputs."""
    s = jax.nn.sigmoid(jnp.matmul(xn, w_router, precision=HI))
    top_s, top_i = jax.lax.top_k(s, a.top_k)
    if a.norm_topk:
        top_s = top_s / jnp.sum(top_s, -1, keepdims=True)
    return top_i, top_s * a.scaling


def _moe(w, xn, a, held, mode, cap):
    """Shared expert + the held experts' part. Returns (y, rows sent to
    each held expert [n])."""
    T = xn.shape[0]
    e0, n = held
    cap = T if cap is None else min(cap, T)
    top_i, top_w = route(xn, w["router"], a)

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

_GQA = ("qkv_proj", "gate_proj", "o_proj")
_KDA = ("qkv_proj", "conv_weight", "decay_down", "decay_up", "A_log",
        "dt_bias", "beta_proj", "gate_down", "gate_up", "o_norm.weight",
        "o_proj")
_MOE = ("router", "experts_gate_up", "experts_down", "shared_gate_up",
        "shared_down")


def layer_names(a, i):
    """{key the equations use: state-dict name} of layer i."""
    base = f"model.layers.{i}."
    mixer, leaves = (("self_attn.", _GQA) if layer_kind(a, i) == "gqa"
                     else ("linear_attn.", _KDA))
    names = {"ln1": base + "input_layernorm.weight",
             "ln2": base + "post_attention_layernorm.weight"}
    names.update({"mixer." + k: base + mixer + k for k in leaves})
    names.update({"mlp." + k: base + "mlp." + k for k in _MOE})
    return names


def _part(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def mixer_half(w, x, kind, a, mode=None):
    """h = x + Mixer(RMSNorm(x)) on x [B, T, H] (float32)."""
    mixer = _gqa_mixer if kind == "gqa" else _kda_mixer
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


def layer(w, x, kind, a, held, mode=None, cap=None):
    """One layer on x [B, T, H] (float32). Returns (y, rows sent to each
    held expert [B, n])."""
    return expert_half(w, mixer_half(w, x, kind, a, mode), a, held, mode,
                       cap)


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
    """Embedding then every layer: (x [B, T, H] before the last norm,
    rows sent [L, B, n])."""
    a = arch(cfg)
    x = jnp.take(state["model.embed_tokens"].astype(F32), ids, axis=0)
    sent = []
    for i in range(cfg["num_hidden_layers"]):
        w = _up({k: state[n] for k, n in layer_names(a, i).items()})
        x, s = layer(w, x, layer_kind(a, i), a, held, mode)
        sent.append(s)
    return x, jnp.stack(sent)


def logits(state, ids, cfg, held, mode=None):
    a = arch(cfg)
    x, _ = hidden_states(state, ids, cfg, held, mode)
    return _mm(_rms(x, state["model.norm.weight"].astype(F32), a.eps),
               state["lm_head"].astype(F32), mode)


def loss(state, ids, cfg, held, mode=None):
    a = arch(cfg)
    x, _ = hidden_states(state, ids, cfg, held, mode)
    return head_loss(state["model.norm.weight"].astype(F32),
                     state["lm_head"].astype(F32), x, ids, a.eps, mode)


def loss_and_grads(state, ids, cfg, held):
    """(loss, {name: gradient}) of the whole model, by autodiff of the
    whole (small sizes: nothing is freed between layers)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda s: loss(s, ids, cfg, held))(_up(state))


# -- the benchmark's own: training steps, a layer at a time -------------------
# (everything above this line is tests/reference/solar_open2.py's text)

import concurrent.futures  # noqa: E402
import functools  # noqa: E402

import numpy as np  # noqa: E402

from chipbench.reference import (  # noqa: E402
    _adamw, _diff_norm, _embed, _embed_grad)


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


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_loss(norm_w, head_w, x, labels, eps, mode):
    return jax.value_and_grad(
        lambda nw, hw, x_: head_loss(nw, hw, x_, labels, eps, mode),
        argnums=(0, 1, 2))(norm_w.astype(F32), head_w.astype(F32), x)


def expert_cap(a, held, tokens):
    """Rows an expert may be sent before `train_steps` refuses to go on:
    four times a uniform router's share, never under 256."""
    return min(tokens, max(256, 4 * -(-tokens * a.top_k // a.n_routed)))


def precompile(shapes, cfg_json, batch, seq, mode=None):
    """Compile the programs `train_steps` will run on [batch, seq] tokens
    from `shapes` ({name: ShapeDtypeStruct} of the state) alone, each kind
    of half layer forward and VJP and the head + loss, into JAX's
    persistent compilation cache, where `train_steps`' own calls find
    them. Nothing runs and nothing is held on the device, so a driver can
    do it on another thread while its program compiles: a "highest"
    float32 product takes the chip's compiler seconds, and these seven
    programs a minute and a half between them."""
    a, held = arch(cfg_json), held_of(cfg_json)
    cap = expert_cap(a, held, seq)
    x = jax.ShapeDtypeStruct((batch, seq, a.hidden), F32)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    jobs = [functools.partial(
        _head_loss.lower, shapes["model.norm.weight"], shapes["lm_head"], x,
        ids, a.eps, mode)]
    seen = set()
    for i in range(cfg_json["num_hidden_layers"]):
        kind = layer_kind(a, i)
        w = {k: shapes[n] for k, n in layer_names(a, i).items()}
        mixer = {k: v for k, v in w.items()
                 if k == "ln1" or k.startswith("mixer.")}
        mlp = {k: v for k, v in w.items()
               if k == "ln2" or k.startswith("mlp.")}
        if kind not in seen:
            jobs += [functools.partial(_mixer_fwd.lower, mixer, x, kind, a,
                                       mode),
                     functools.partial(_mixer_bwd.lower, mixer, x, x, kind,
                                       a, mode)]
        if not seen:
            jobs += [functools.partial(_expert_fwd.lower, mlp, x, a, held,
                                       mode, cap),
                     functools.partial(_expert_bwd.lower, mlp, x, x, a, held,
                                       mode, cap)]
        seen.add(kind)

    def build(lower):
        with jax.default_matmul_precision("highest"):
            lower().compile()

    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        list(pool.map(build, jobs))


def train_steps(make_state, batches, cfg_json, trainer, mode=None,
                keep=np.asarray):
    """Follow `len(batches)` training steps from the state `make_state()`
    returns, as `chipbench.reference.train_steps` does for the dense
    decoder: parameters and AdamW moments stored in the dtype the
    configuration trains in, all arithmetic float32; here HALF a layer at
    a time (mixer, then experts), and each half's input is kept for the
    backward on the HOST (`keep`): at 32768 tokens neither a whole
    layer's VJP nor the float32 stream of every layer fits beside the
    state. Returns {"losses", "grad_norms", "delta_norms", "expert_rows"
    (the most rows any held expert was sent)}."""
    a, held = arch(cfg_json), held_of(cfg_json)
    n_layers = cfg_json["num_hidden_layers"]
    hp = (float(trainer["beta1"]), float(trainer["beta2"]),
          float(trainer["epsilon"]), float(trainer["weight_decay"]))
    lr = np.float32(trainer["learning_rate"])
    p = make_state()
    names = [layer_names(a, i) for i in range(n_layers)]
    trained = {"model.embed_tokens", "model.norm.weight", "lm_head"}
    trained.update(n for per in names for n in per.values())
    mom, losses, grad_norms, most = {}, [], {}, 0

    def half(i, which):
        """The weights one half of layer i reads: its norm and its part."""
        norm = "ln1" if which == "mixer" else "ln2"
        return {k: p[n] for k, n in names[i].items()
                if k == norm or k.startswith(which + ".")}

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
                kind = layer_kind(a, i)
                xs.append(keep(x))
                x = _mixer_fwd(half(i, "mixer"), x, kind, a, mode)
                xs.append(keep(x))
                x, sent = _expert_fwd(half(i, "mlp"), x, a, held, mode, cap)
                most = max(most, int(jnp.max(sent)))
                if most > cap:
                    raise AssertionError(
                        f"reference: an expert of layer {i} was sent {most} "
                        f"rows, more than the {cap} it multiplies")
            loss, (dn, dh, dx) = _head_loss(
                p["model.norm.weight"], p["lm_head"], x, ids, a.eps, mode)
            del x
            losses.append(loss)
            update("model.norm.weight", dn, t)
            update("lm_head", dh, t)
            for i in reversed(range(n_layers)):
                dw, dx = _expert_bwd(half(i, "mlp"), jnp.asarray(xs.pop()),
                                     dx, a, held, mode, cap)
                for k, g in dw.items():
                    update(names[i][k], g, t)
                dw, dx = _mixer_bwd(half(i, "mixer"), jnp.asarray(xs.pop()),
                                    dx, layer_kind(a, i), a, mode)
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
            "expert_rows": most}
