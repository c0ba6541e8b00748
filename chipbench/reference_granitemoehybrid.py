"""The plain reference of the Granite-4.0-H block (`granitemoehybrid`): a
pre-norm decoder whose mixers are of two kinds in a fixed pattern
(`layer_types`: Mamba-2 state-space layers, and softmax grouped-query
attention WITHOUT rotary), each followed by a dense SwiGLU MLP, with
muP-style multipliers and a head tied to the embedding. Written from the
equations in `jax.numpy` float32 at matmul precision "highest": no
kernel, no chunking of the recurrence (the state-space layer runs token
by token, so that the program's chunked form is itself tested), nothing
imported from the program.

Equations (x the layer input, one sequence, eps the config's, r the
`residual_multiplier`):
  embed   h0 = E[ids] * embedding_multiplier
  block   h = x + r Mixer(RMSNorm(x));  y = h + r MLP(RMSNorm(h))
  MLP     (silu(x Wg) * (x Wu)) Wd
  GQA     q = x Wq [nh x d], k, v = x Wk, x Wv [kvh x d];
          a = causal softmax(q k^T * attention_multiplier) v, no rotary,
          no bias;  out = a Wo
  Mamba   [z | xBC | dt] = x W_in   (inner | inner + 2 n | heads);
          xBC = silu(conv(xBC) + b)  (causal depthwise conv, 4 taps, over
          the x | B | C channels together);  [x | B | C] = xBC, x as
          heads of p channels, B, C in R^n shared by every head;
          dt_t = softplus(dt_t + dt_bias),  A = -exp(A_log)   (a head);
          S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T   (S in R^{p x n} a
          head, zero at the sequence's start);  y_t = S_t C_t + D x_t;
          out = RMSNorm_w(y * silu(z)) W_out   (the gate BEFORE the norm,
          the norm over all inner channels: one group)
  head    logits = RMSNorm(h) E^T / logits_scaling  (E the embedding)

Departures, each because plain f32 at the benchmark's sizes would not
fit one chip, none changing a value: attention runs one KV head's group
and one block of query rows at a time, the MLP, the head and the loss
one block of rows at a time, the recurrence's backward recomputes inside
blocks of tokens, and a state-space mixer runs a block of tokens at a
time, handing on the state and the convolution's last three rows.

State-dict layout (matrices [in, out]): `qkv_proj` holds q | k | v
columns, `gate_up_proj` gate | up columns, `in_proj` z | x | B | C | dt
columns, `conv_weight` is [4, channels] with tap j on x_{t-3+j},
`model.embed_tokens` [vocab, hidden] is also the head.

`mode` computes every weight matmul in a lower precision (the control of
`correct`): "fp8" (e4m3, per-row / per-column scales), "int8" or "bf16";
the gradient passes straight through.
"""
from __future__ import annotations

import concurrent.futures
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import (_adamw, _diff_norm, _embed, _embed_grad,
                                 _fake_quant)

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


class Arch(NamedTuple):
    hidden: int
    nh: int            # softmax layers: query heads
    kvh: int
    d: int
    heads: int         # state-space heads
    p: int             # their width
    n: int             # the state's size
    taps: int
    eps: float
    embed_mult: float
    attn_mult: float
    res_mult: float
    logit_div: float
    layer_types: tuple


def arch(cfg):
    """The static sizes the equations need, from a configuration file."""
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("the equations here share one B and C among all "
                         "heads: mamba_n_groups must be 1")
    return Arch(
        hidden=cfg["hidden_size"], nh=cfg["num_attention_heads"],
        kvh=cfg["num_key_value_heads"],
        d=cfg["hidden_size"] // cfg["num_attention_heads"],
        heads=cfg["mamba_n_heads"], p=cfg["mamba_d_head"],
        n=cfg["mamba_d_state"], taps=cfg["mamba_d_conv"],
        eps=float(cfg["rms_norm_eps"]),
        embed_mult=float(cfg["embedding_multiplier"]),
        attn_mult=float(cfg["attention_multiplier"]),
        res_mult=float(cfg["residual_multiplier"]),
        logit_div=float(cfg["logits_scaling"]),
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]))


def layer_kind(a, i):
    return a.layer_types[i]


def _mm(x, w, mode=None):
    return jnp.matmul(_fake_quant(x, mode, -1), _fake_quant(w, mode, 0),
                      precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _by_rows(fn, x, block):
    """fn over x [T, ...] a block of rows at a time; the backward makes a
    block again instead of keeping it."""
    T = x.shape[0]
    if T % block:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape((T // block, block) + x.shape[1:]))
    return out.reshape((T,) + out.shape[2:])


# -- softmax attention without rotary ----------------------------------------

def _attend(q, k, v, scale, block=256):
    """One KV head's group: q [T, g, d], k, v [T, d] -> [T, g, d], a
    block of query rows at a time."""
    T, g, d = q.shape
    if T % block:
        block = T
    cols = jnp.arange(T)

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        s = jnp.einsum("tgd,sd->gts", qs, k, precision=HI) * scale
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
        "o": w["o_proj"].reshape(kvh, g * d, H),
    }

    def group(xn, wg):
        q = _mm(xn, wg["q"], mode).reshape(-1, g, d)
        o = _attend(q, _mm(xn, wg["k"], mode), _mm(xn, wg["v"], mode),
                    a.attn_mult)
        return _mm(o.reshape(-1, g * d), wg["o"], mode)

    def body(acc, wg):
        return acc + jax.checkpoint(group)(xn, wg), None

    return jax.lax.scan(body, jnp.zeros(xn.shape, F32), grouped)[0]


# -- the state-space layer ---------------------------------------------------

def ssm_recurrence(S, x, dt, A, Bm, Cm, D, block=128):
    """The state-space recurrence, token by token, from the state S
    [h, p, n]. x [T, h, p], dt [T, h] (after its softplus), A [h] (< 0),
    Bm, Cm [T, n], D [h]. Returns (the state after the last token,
    y [T, h, p]). The backward keeps the state once a block of tokens and
    recomputes inside a block."""
    T = x.shape[0]

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        S = (S * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return S, jnp.einsum("hpn,n->hp", S, c_t,
                             precision=HI) + D[:, None] * x_t

    xs = (x, dt, Bm, Cm)
    if T % block:
        return jax.lax.scan(step, S, xs)
    xs = jax.tree_util.tree_map(
        lambda v: v.reshape((T // block, block) + v.shape[1:]), xs)
    inner = jax.checkpoint(lambda S, blk: jax.lax.scan(step, S, blk))
    S, y = jax.lax.scan(inner, S, xs)
    return S, y.reshape((T,) + y.shape[2:])


def _mamba_mixer(w, xn, a, mode, block=2048):
    """Every stage but the recurrence is a token's own (the convolution
    reads three tokens back), so the mixer runs a block of tokens at a
    time and hands on the state and the projection's last rows."""
    inner, n = a.heads * a.p, a.n
    proj, taps = w["in_proj"], w["conv_weight"]
    A = -jnp.exp(w["A_log"])
    T = xn.shape[0]
    if T % block:
        block = T

    def rows(carry, xb):
        S, before = carry
        z = _mm(xb, proj[:, :inner], mode)
        pre = jnp.concatenate(
            [before, _mm(xb, proj[:, inner:2 * inner + 2 * n], mode)])
        # tap j multiplies x_{t - (taps-1) + j}; zeros before t = 0
        xbc = jax.nn.silu(sum(pre[j:j + block] * taps[j]
                              for j in range(a.taps)) + w["conv_bias"])
        dt = jax.nn.softplus(_mm(xb, proj[:, 2 * inner + 2 * n:], mode)
                             + w["dt_bias"])
        S, y = ssm_recurrence(
            S, xbc[:, :inner].reshape(-1, a.heads, a.p), dt, A,
            xbc[:, inner:inner + n], xbc[:, inner + n:], w["D"])
        g = _rms(y.reshape(-1, inner) * jax.nn.silu(z), w["norm.weight"],
                 a.eps)
        return (S, pre[block:]), _mm(g, w["out_proj"], mode)

    start = (jnp.zeros((a.heads, a.p, n), F32),
             jnp.zeros((a.taps - 1, inner + 2 * n), F32))
    out = jax.lax.scan(jax.checkpoint(rows), start,
                       xn.reshape(T // block, block, -1))[1]
    return out.reshape(T, -1)


# -- a layer, the head, the whole ---------------------------------------------

_GQA = ("qkv_proj", "o_proj")
_MAMBA = ("in_proj", "conv_weight", "conv_bias", "A_log", "dt_bias", "D",
          "norm.weight", "out_proj")
_MLP = ("gate_up_proj", "down_proj")


def layer_names(a, i):
    """{key the equations use: state-dict name} of layer i."""
    base = f"model.layers.{i}."
    mixer, leaves = (("self_attn.", _GQA)
                     if layer_kind(a, i) == "attention"
                     else ("mamba.", _MAMBA))
    names = {"ln1": base + "input_layernorm.weight",
             "ln2": base + "post_attention_layernorm.weight"}
    names.update({"mixer." + k: base + mixer + k for k in leaves})
    names.update({"mlp." + k: base + "shared_mlp." + k for k in _MLP})
    return names


def _part(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def mixer_half(w, x, kind, a, mode=None):
    """h = x + r Mixer(RMSNorm(x)) on x [B, T, H] (float32)."""
    mixer = _gqa_mixer if kind == "attention" else _mamba_mixer
    return jax.lax.map(
        lambda xr: xr + a.res_mult * mixer(
            _part(w, "mixer."), _rms(xr, w["ln1"], a.eps), a, mode), x)


def mlp_half(w, h, a, mode=None, block=4096):
    """y = h + r MLP(RMSNorm(h)) on h [B, T, H] (float32), a block of rows
    at a time."""
    gu, down = w["mlp.gate_up_proj"], w["mlp.down_proj"]
    m = down.shape[0]

    def rows(hb):
        act = _mm(_rms(hb, w["ln2"], a.eps), gu, mode)
        return hb + a.res_mult * _mm(
            jax.nn.silu(act[..., :m]) * act[..., m:], down, mode)

    return jax.lax.map(lambda hr: _by_rows(rows, hr, block), h)


def head_loss(norm_w, table, x, labels, a, mode=None, block=1024):
    """Mean next-token cross-entropy over x [B, T, H], labels [B, T], the
    head the embedding table [V, H]: a block of rows at a time (a
    sequence's last position has no label and counts nothing)."""
    B, T, H = x.shape
    xr = _rms(x, norm_w, a.eps).reshape(-1, H)
    tgt = jnp.concatenate([labels[:, 1:], jnp.full((B, 1), -1, labels.dtype)],
                          axis=1).reshape(-1)
    if (B * T) % block:
        block = B * T

    def rows(args):
        xb, tb = args
        lg = _mm(xb, table.T, mode) / a.logit_div
        lse = jax.nn.logsumexp(lg, axis=-1)
        own = jnp.take_along_axis(lg, jnp.maximum(tb, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(tb >= 0, lse - own, 0.0))

    parts = jax.lax.map(jax.checkpoint(rows),
                        (xr.reshape(-1, block, H), tgt.reshape(-1, block)))
    return jnp.sum(parts) / (B * (T - 1))


def _up(w):
    return {k: v.astype(F32) for k, v in w.items()}


def hidden_states(state, ids, cfg, mode=None):
    """Embedding then every layer: x [B, T, H] before the last norm."""
    a = arch(cfg)
    x = jnp.take(state["model.embed_tokens"].astype(F32), ids,
                 axis=0) * a.embed_mult
    for i in range(cfg["num_hidden_layers"]):
        w = _up({k: state[n] for k, n in layer_names(a, i).items()})
        x = mlp_half(w, mixer_half(w, x, layer_kind(a, i), a, mode), a, mode)
    return x


def logits(state, ids, cfg, mode=None):
    a = arch(cfg)
    x = hidden_states(state, ids, cfg, mode)
    return _mm(_rms(x, state["model.norm.weight"].astype(F32), a.eps),
               state["model.embed_tokens"].astype(F32).T,
               mode) / a.logit_div


def loss(state, ids, cfg, mode=None):
    x = hidden_states(state, ids, cfg, mode)
    return head_loss(state["model.norm.weight"].astype(F32),
                     state["model.embed_tokens"].astype(F32), x, ids,
                     arch(cfg), mode)


def loss_and_grads(state, ids, cfg):
    """(loss, {name: gradient}) of the whole model, by autodiff of the
    whole (small sizes: nothing is freed between layers). The table's
    gradient is the sum of its two uses."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda s: loss(s, ids, cfg))(_up(state))


# -- training steps, half a layer at a time -----------------------------------

@functools.partial(jax.jit, static_argnames=("kind", "a", "mode"))
def _mixer_fwd(w, x, kind, a, mode):
    return mixer_half(_up(w), x, kind, a, mode)


@functools.partial(jax.jit, static_argnames=("kind", "a", "mode"))
def _mixer_bwd(w, x, dh, kind, a, mode):
    _, vjp = jax.vjp(lambda w_, x_: mixer_half(w_, x_, kind, a, mode),
                     _up(w), x)
    return vjp(dh)                              # (dw, dx)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _mlp_fwd(w, h, a, mode):
    return mlp_half(_up(w), h, a, mode)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _mlp_bwd(w, h, dy, a, mode):
    _, vjp = jax.vjp(lambda w_, h_: mlp_half(w_, h_, a, mode), _up(w), h)
    return vjp(dy)                              # (dw, dh)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _head_loss(norm_w, table, x, labels, a, mode):
    return jax.value_and_grad(
        lambda nw, tb, x_: head_loss(nw, tb, x_, labels, a, mode),
        argnums=(0, 1, 2))(norm_w.astype(F32), table.astype(F32), x)


@functools.partial(jax.jit, static_argnames=("mult",))
def _tied_grad(table, ids, dx, d_head, mult):
    """The table's gradient: the head's plus the lookup's."""
    return d_head + _embed_grad(table, ids, dx * mult)


def _halves(names, shapes):
    """(mixer's, MLP's) {key: shape} of one layer's names."""
    w = {k: shapes[n] for k, n in names.items()}
    return ({k: v for k, v in w.items()
             if k == "ln1" or k.startswith("mixer.")},
            {k: v for k, v in w.items()
             if k == "ln2" or k.startswith("mlp.")})


def precompile(shapes, cfg_json, batch, seq, mode=None):
    """Compile the programs `train_steps` will run on [batch, seq] tokens
    from `shapes` ({name: ShapeDtypeStruct} of the state) alone, each kind
    of half layer forward and VJP and the head + loss, into JAX's
    persistent compilation cache, where `train_steps`' own calls find
    them. Nothing runs and nothing is held on the device, so a driver can
    do it on another thread while its program compiles."""
    a = arch(cfg_json)
    x = jax.ShapeDtypeStruct((batch, seq, a.hidden), F32)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    jobs = [functools.partial(
        _head_loss.lower, shapes["model.norm.weight"],
        shapes["model.embed_tokens"], x, ids, a, mode)]
    seen = set()
    for i in range(cfg_json["num_hidden_layers"]):
        kind = layer_kind(a, i)
        mixer, mlp = _halves(layer_names(a, i), shapes)
        if kind not in seen:
            jobs += [functools.partial(_mixer_fwd.lower, mixer, x, kind, a,
                                       mode),
                     functools.partial(_mixer_bwd.lower, mixer, x, x, kind,
                                       a, mode)]
        if not seen:
            jobs += [functools.partial(_mlp_fwd.lower, mlp, x, a, mode),
                     functools.partial(_mlp_bwd.lower, mlp, x, x, a, mode)]
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
    a time (mixer, then MLP), and each half's input is kept for the
    backward on the HOST (`keep`): at 32768 tokens the float32 stream of
    every layer does not fit beside the state. The embedding table's
    gradient is the head's plus the lookup's, applied once. Returns
    {"losses", "grad_norms", "delta_norms", "expert_rows" (empty: the
    model has no experts)}."""
    a = arch(cfg_json)
    n_layers = cfg_json["num_hidden_layers"]
    hp = (float(trainer["beta1"]), float(trainer["beta2"]),
          float(trainer["epsilon"]), float(trainer["weight_decay"]))
    lr = np.float32(trainer["learning_rate"])
    p = make_state()
    names = [layer_names(a, i) for i in range(n_layers)]
    trained = {"model.embed_tokens", "model.norm.weight"}
    trained.update(n for per in names for n in per.values())
    mom, losses, grad_norms = {}, [], {}

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
            x = _embed(p["model.embed_tokens"], ids) * a.embed_mult
            xs = []
            for i in range(n_layers):
                xs.append(keep(x))
                x = _mixer_fwd(half(i, "mixer"), x, layer_kind(a, i), a,
                               mode)
                xs.append(keep(x))
                x = _mlp_fwd(half(i, "mlp"), x, a, mode)
            loss, (dn, d_head, dx) = _head_loss(
                p["model.norm.weight"], p["model.embed_tokens"], x, ids, a,
                mode)
            del x
            losses.append(loss)
            update("model.norm.weight", dn, t)
            for i in reversed(range(n_layers)):
                dw, dx = _mlp_bwd(half(i, "mlp"), jnp.asarray(xs.pop()), dx,
                                  a, mode)
                for k, g in dw.items():
                    update(names[i][k], g, t)
                dw, dx = _mixer_bwd(half(i, "mixer"), jnp.asarray(xs.pop()),
                                    dx, layer_kind(a, i), a, mode)
                for k, g in dw.items():
                    update(names[i][k], g, t)
            update("model.embed_tokens",
                   _tied_grad(p["model.embed_tokens"], ids, dx, d_head,
                              a.embed_mult), t)
        del mom, xs, dx, dw, dn, d_head
        start = make_state()
        delta = {k: _diff_norm(p[k], start[k]) for k in sorted(trained)}
    return {"losses": [float(x) for x in losses],
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()},
            "expert_rows": []}
