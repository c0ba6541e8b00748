"""The plain reference: a dense pre-norm decoder (RMSNorm, rotate-half
RoPE, grouped-query causal attention, SwiGLU, untied head) written from
the architecture's equations in `jax.numpy` float32 at matmul precision
"highest". No kernel, no cache, no batching, nothing imported from the
program. Weights are the benchmark's own (`weights.generator`), in the
state-dict layout the configuration files describe: `qkv_proj` holds the
q | k | v columns, `gate_up_proj` the gate | up columns, matrices are
[in, out].

It runs one layer at a time, upcasting that layer's weights only, so it
fits beside (or after) bf16 state on one chip.

Departures from "float32 everywhere", both because the configuration
states them: parameters and AdamW moments are STORED in the dtype the
configuration trains in (bf16, float32 for norm weights) between steps,
as a trainer without master weights stores them; all arithmetic on them
is float32.

`mode` computes every weight matmul in a lower precision (the control of
`correct`): "fp8" (e4m3, per-row / per-column scales), "int8" (the same
scales, round to nearest) or "bf16". The rounding is applied to the
values, the gradient passes straight through.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def arch(cfg_json):
    """The static sizes the equations need, from a configuration file."""
    nh = cfg_json["num_attention_heads"]
    return (nh, cfg_json["num_key_value_heads"],
            cfg_json["hidden_size"] // nh, float(cfg_json["rms_norm_eps"]),
            float(cfg_json["rope_theta"]), cfg_json["intermediate_size"])


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


def _mm(a, w, mode):
    return jnp.matmul(_fake_quant(a, mode, -1), _fake_quant(w, mode, 0),
                      precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, heads, d], pos [T]: rotate-half RoPE."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = pos.astype(F32)[:, None] * inv[None]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v):
    """Causal grouped-query attention of one sequence: q [T, nh, d],
    k, v [T, kvh, d] -> [T, nh, d]; one KV head's group at a time."""
    T, nh, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(T, kvh, nh // kvh, d).transpose(1, 2, 0, 3)
    mask = jnp.tril(jnp.ones((T, T), bool))

    def group(args):
        qh, kh, vh = args                       # [g, T, d], [T, d], [T, d]
        s = jnp.einsum("gtd,sd->gts", qh, kh, precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->gtd", p, vh, precision=HI)

    # checkpointed: the backward recomputes a group's [g, T, T] scores
    # and keeps no group's alive, so a 4096-token layer fits the chip
    o = jax.lax.map(jax.checkpoint(group),
                    (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(2, 0, 1, 3).reshape(T, nh, d)


def _layer(w, x, a, mode):
    """One decoder layer on x [B, T, H] (float32)."""
    nh, kvh, d, eps, theta, m = a
    pos = jnp.arange(x.shape[1])

    def one(xr):
        qkv = _mm(_rms(xr, w["ln1"], eps), w["qkv"], mode)
        q = qkv[:, :nh * d].reshape(-1, nh, d)
        k = qkv[:, nh * d:(nh + kvh) * d].reshape(-1, kvh, d)
        v = qkv[:, (nh + kvh) * d:].reshape(-1, kvh, d)
        o = _attend(_rope(q, pos, theta), _rope(k, pos, theta), v)
        h = xr + _mm(o.reshape(-1, nh * d), w["o"], mode)
        gu = _mm(_rms(h, w["ln2"], eps), w["gu"], mode)
        return h + _mm(jax.nn.silu(gu[:, :m]) * gu[:, m:], w["down"], mode)

    return jax.lax.map(one, x)


_LEAVES = {"ln1": "input_layernorm.weight", "qkv": "self_attn.qkv_proj",
           "o": "self_attn.o_proj", "ln2": "post_attention_layernorm.weight",
           "gu": "mlp.gate_up_proj", "down": "mlp.down_proj"}


def layer_names(i):
    return {k: f"model.layers.{i}.{v}" for k, v in _LEAVES.items()}


def _up(w):
    return {k: v.astype(F32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _layer_fwd(w, x, a, mode):
    return _layer(_up(w), x, a, mode)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _layer_bwd(w, x, dy, a, mode):
    _, vjp = jax.vjp(lambda w_, x_: _layer(w_, x_, a, mode), _up(w), x)
    return vjp(dy)                              # (dw, dx)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_loss(norm_w, head_w, x, labels, eps, mode):
    """Mean next-token cross-entropy and its gradients."""
    def f(nw, hw, x_):
        lg = _mm(_rms(x_[:, :-1], nw, eps), hw, mode)
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, labels[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(lse - tgt)
    return jax.value_and_grad(f, argnums=(0, 1, 2))(
        norm_w.astype(F32), head_w.astype(F32), x)


@jax.jit
def _embed(table, ids):
    return jnp.take(table, ids, axis=0).astype(F32)


@jax.jit
def _embed_grad(table, ids, dx):
    return jnp.zeros(table.shape, F32).at[ids].add(dx)


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(1, 2))
def _adamw(p, m, v, g, lr, t, hp):
    """Decoupled-decay AdamW in float32 on stored-dtype state. Returns
    the new (p, m, v) in their stored dtypes and |g| of this leaf."""
    b1, b2, eps, wd = hp
    p32 = p.astype(F32) * (1.0 - lr * wd)
    m32 = b1 * m.astype(F32) + (1 - b1) * g
    v32 = b2 * v.astype(F32) + (1 - b2) * g * g
    new = p32 - lr * (m32 / (1 - b1 ** t)) / (
        jnp.sqrt(v32 / (1 - b2 ** t)) + eps)
    return (new.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype),
            jnp.sqrt(jnp.sum(g * g)))


@jax.jit
def _diff_norm(a, b):
    d = a.astype(F32) - b.astype(F32)
    return jnp.sqrt(jnp.sum(d * d))


def train_steps(make_state, batches, cfg_json, trainer, mode=None,
                keep=lambda x: x):
    """Follow `len(batches)` training steps from the state `make_state()`
    returns ({name: array}; called again at the end for the start the
    change is measured from, so that no second copy lives through the
    steps). batches [n, B, T] int32. `keep` is applied to each layer's
    input before it is kept for the backward (a cell across chips
    spreads it over them). Returns
    {"losses": [...], "grad_norms": {name: |g| at step 1},
     "delta_norms": {name: |p_n - p_0|}}."""
    a = arch(cfg_json)
    n_layers = cfg_json["num_hidden_layers"]
    hp = (float(trainer["beta1"]), float(trainer["beta2"]),
          float(trainer["epsilon"]), float(trainer["weight_decay"]))
    lr = np.float32(trainer["learning_rate"])
    p = make_state()
    mom, losses, grad_norms = {}, [], {}

    def update(name, g, t):
        m, v = mom.pop(name, None) or (jnp.zeros_like(p[name]),
                                       jnp.zeros_like(p[name]))
        p[name], m, v, gn = _adamw(p[name], m, v, g, lr, np.float32(t), hp)
        mom[name] = (m, v)
        if t == 1:
            grad_norms[name] = gn

    with jax.default_matmul_precision("highest"):
        for t, ids in enumerate(jnp.asarray(batches), start=1):
            xs = [_embed(p["model.embed_tokens"], ids)]
            for i in range(n_layers):
                w = {k: p[n] for k, n in layer_names(i).items()}
                xs.append(keep(_layer_fwd(w, xs[-1], a, mode)))
            loss, (dn, dh, dx) = _head_loss(
                p["model.norm.weight"], p["lm_head"], xs.pop(), ids,
                a[3], mode)
            losses.append(loss)
            update("model.norm.weight", dn, t)
            update("lm_head", dh, t)
            for i in reversed(range(n_layers)):
                names = layer_names(i)
                dw, dx = _layer_bwd({k: p[n] for k, n in names.items()},
                                    xs.pop(), dx, a, mode)
                for k, n in names.items():
                    update(n, dw[k], t)
            update("model.embed_tokens",
                   _embed_grad(p["model.embed_tokens"], ids, dx), t)
        del mom, xs, dx, dw, dn, dh
        start = make_state()
        delta = {k: _diff_norm(p[k], start[k]) for k in start}
    return {"losses": [float(x) for x in losses],
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()}}


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_rows(norm_w, head_w, x, rows, eps, mode):
    return _mm(_rms(jnp.take(x, rows, axis=0), norm_w.astype(F32), eps),
               head_w.astype(F32), mode)


def logits_at(state, cfg_json, tokens, rows, mode=None, pad_to=512):
    """Full forward over one token sequence; float32 logits [len(rows), V]
    at positions `rows`. The sequence is right-padded to a multiple of
    `pad_to` (causal: padding changes nothing before it) and `rows` to a
    multiple of 64, so a handful of programs serve every length."""
    a = arch(cfg_json)
    n = len(tokens)
    T = -(-n // pad_to) * pad_to
    ids = np.zeros((1, T), np.int32)
    ids[0, :n] = tokens
    r = np.zeros((-(-len(rows) // 64) * 64,), np.int32)
    r[:len(rows)] = rows
    with jax.default_matmul_precision("highest"):
        x = _embed(state["model.embed_tokens"], jnp.asarray(ids))
        for i in range(cfg_json["num_hidden_layers"]):
            w = {k: state[nm] for k, nm in layer_names(i).items()}
            x = _layer_fwd(w, x, a, mode)
        lg = _head_rows(state["model.norm.weight"], state["lm_head"], x[0],
                        jnp.asarray(r), a[3], mode)
    return lg[:len(rows)]


def worst_leaf_gap(program, reference):
    """Largest over leaves of |program's norm - reference's norm| against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). Returns (gap, leaf)."""
    floor = float(np.median(list(reference.values())))
    worst, where = 0.0, None
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor)
        if not gap <= worst:                    # a NaN gap is the worst
            worst, where = float(gap), name
            if math.isnan(gap):
                return float("inf"), name
    return worst, where
