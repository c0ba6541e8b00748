#!/usr/bin/env python
"""On-chip train-step time breakdown (diagnosis tool for the 350m
MFU regression of ROADMAP.md S2; ref: the reference's op-benchmark CI
`tools/ci_op_benchmark.sh` plays this per-op timing role).

Times, on the real chip, each piece of the bench train step so a
regression can be attributed instead of guessed at:

  dispatch      — trivial jitted fn (executor round-trip floor)
  fwd           — model forward + loss only
  fwdbwd        — forward + backward (no optimizer)
  step          — full TrainStep (fwd + bwd + AdamW), the bench number
  step_unfused  — same with r2-era unfused qkv/mlp layouts (BENCH_UNFUSED=1)
  attn_kernel   — flash-attention kernel fwd+bwd at bench shapes
  attn_flash_b1 / attn_dense_b1 — flash vs dense-XLA attention at B=1
  mlp           — one SwiGLU MLP fwd+bwd
  lmhead_ce     — logits matmul + fused (Pallas) CE fwd+bwd
  lmhead_ce_xla — same head through plain-XLA log_softmax CE
  adamw         — optimizer update alone on the full param tree

Prints one JSON line per piece: {"piece": ..., "ms": ..., "iters": N}.
Timing forces a host transfer per iteration batch.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, iters, *args):
    """Median-of-3 batches of `iters` calls, host-transfer fenced."""
    import jax
    out = fn(*args)
    jax.tree_util.tree_map(
        lambda x: float(x.reshape(-1)[0]) if hasattr(x, "reshape") else x,
        out)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        leaf = jax.tree_util.tree_leaves(out)[0]
        float(leaf.reshape(-1)[0])
        times.append((time.perf_counter() - t0) / iters)
    return sorted(times)[1] * 1e3


def main():
    import numpy as np

    size = os.environ.get("BENCH_MODEL", "350m")
    B = int(os.environ.get("BENCH_BATCH", "4"))
    S = int(os.environ.get("BENCH_SEQ", "2048"))
    iters = int(os.environ.get("BENCH_STEPS", "8"))

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.models import llama as L

    dev = jax.devices()[0]
    print(f"device: {getattr(dev, 'device_kind', dev.platform)}",
          file=sys.stderr)

    def emit(piece, ms, n=iters):
        print(json.dumps({"piece": piece, "ms": round(ms, 3), "iters": n}),
              flush=True)

    # dispatch floor
    one = jnp.float32(1.0)
    triv = jax.jit(lambda x: x + 1)
    emit("dispatch", _time(triv, iters, one))

    paddle.seed(0)
    cfg = {"tiny": L.llama_tiny, "350m": L.llama_350m,
           "1b": L.llama_1b, "7b": L.llama_7b}[size]()
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, S)
    model = L.LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    ids = paddle.to_tensor(ids_np)

    state = {k: t.data for k, t in model.state_dict().items()}
    n_params = sum(int(np.prod(t.shape)) for t in model.parameters())
    print(f"n_params: {n_params}", file=sys.stderr)

    # fwd only
    def fwd(state, ids):
        from paddle_tpu.framework import core
        from paddle_tpu.tensor import Tensor
        with model.use_state(state), core.no_grad_guard():
            return model.loss(Tensor(ids), Tensor(ids)).data

    jfwd = jax.jit(fwd)
    emit("fwd", _time(jfwd, iters, state, ids.data))

    # fwd + bwd (grads wrt all params), no optimizer
    from paddle_tpu.tensor import Parameter
    pkeys = [k for k, t in model.state_dict().items()
             if isinstance(t, Parameter) and not t.stop_gradient]

    def loss_of(params, other, ids):
        st = dict(other)
        st.update(params)
        from paddle_tpu.tensor import Tensor
        with model.use_state(st):
            return model.loss(Tensor(ids), Tensor(ids)).data

    params = {k: state[k] for k in pkeys}
    other = {k: v for k, v in state.items() if k not in pkeys}
    jgrad = jax.jit(lambda p, o, i: jax.grad(loss_of)(p, o, i))
    emit("fwdbwd", _time(jgrad, iters, params, other, ids.data))

    # full TrainStep timing, shared by the fused (bench-path) and
    # unfused (r2-layout) variants so the two stay comparable
    def _time_full_step(size, S, iters, use_model=None, **cfg_kw):
        if use_model is None:
            paddle.seed(0)
            cfg_v = {"tiny": L.llama_tiny, "350m": L.llama_350m,
                     "1b": L.llama_1b, "7b": L.llama_7b}[size](**cfg_kw)
            cfg_v.max_position_embeddings = max(
                cfg_v.max_position_embeddings, S)
            use_model = L.LlamaForCausalLM(cfg_v)
        opt_v = popt.AdamW(learning_rate=3e-4,
                           parameters=use_model.parameters(),
                           weight_decay=0.1)
        step_v = paddle.jit.TrainStep(
            use_model, opt_v, lambda i, l: use_model.loss(i, l))
        for _ in range(6):
            loss = step_v(ids, ids)
        float(loss.numpy())
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step_v(ids, ids)
        float(loss.numpy())
        return (time.perf_counter() - t0) / iters * 1e3

    # full step (bench path)
    emit("step", _time_full_step(size, S, iters, use_model=model))

    # one attention layer fwd+bwd at bench shapes
    from paddle_tpu.kernels import flash_attention as fa
    H, D, kvh = cfg.num_attention_heads, cfg.head_dim, cfg.kv_heads
    kq = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kq[1], (B, S, kvh, D), jnp.bfloat16)
    v = jax.random.normal(kq[2], (B, S, kvh, D), jnp.bfloat16)
    if fa.supported(q.shape, k.shape, True):
        jattn = jax.jit(jax.grad(lambda q_: fa.flash_attention_bshd(
            q_, k, v, causal=True).astype(jnp.float32).sum()))
        emit("attn_kernel", _time(jattn, iters, q))

    # one SwiGLU MLP fwd+bwd
    h, inter = cfg.hidden_size, cfg.intermediate_size
    wg = jax.random.normal(jax.random.PRNGKey(1), (h, inter), jnp.bfloat16)
    wu = jax.random.normal(jax.random.PRNGKey(2), (h, inter), jnp.bfloat16)
    wd = jax.random.normal(jax.random.PRNGKey(3), (inter, h), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(4), (B * S, h), jnp.bfloat16)

    def mlp(x):
        g = jax.nn.silu((x @ wg).astype(jnp.float32)).astype(x.dtype)
        return ((g * (x @ wu)) @ wd).astype(jnp.float32).sum()

    emit("mlp", _time(jax.jit(jax.grad(mlp)), iters, x))

    # lm head + fused CE fwd+bwd, vs the plain-XLA CE it replaced
    # (815228d landed the Pallas CE between the r2 measurement and r4 —
    # this pair attributes its real on-chip cost)
    V = cfg.vocab_size
    wlm = jax.random.normal(jax.random.PRNGKey(5), (h, V), jnp.bfloat16)
    lbl = jnp.asarray(rng.integers(0, V, (B * S,)).astype(np.int32))

    # call the Pallas kernel DIRECTLY: F.cross_entropy routes by the
    # FLAGS_use_fused_ce default (False since r5), which would make
    # this A/B compare XLA against XLA
    from paddle_tpu.kernels.cross_entropy import fused_cross_entropy

    def head(x):
        lg = (x @ wlm)
        return fused_cross_entropy(lg.astype(jnp.float32), lbl,
                                   -100).mean()

    emit("lmhead_ce", _time(jax.jit(jax.grad(head)), iters, x))

    def head_xla(x):
        lg = (x @ wlm).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, lbl[:, None], axis=-1)[:, 0]
        return jnp.mean(nll)

    emit("lmhead_ce_xla", _time(jax.jit(jax.grad(head_xla)), iters, x))

    # flash vs dense-XLA attention at B=1 (dense at full B would chance
    # an HBM blowup; the per-call ratio is what matters)
    if fa.supported(q.shape, k.shape, True):
        q1, k1, v1 = q[:1], k[:1], v[:1]
        jf1 = jax.jit(jax.grad(lambda q_: fa.flash_attention_bshd(
            q_, k1, v1, causal=True).astype(jnp.float32).sum()))
        emit("attn_flash_b1", _time(jf1, iters, q1))

        def dense(q_):
            qt = jnp.swapaxes(q_, 1, 2).astype(jnp.float32)
            kt = jnp.swapaxes(k1, 1, 2).astype(jnp.float32)
            vt = jnp.swapaxes(v1, 1, 2).astype(jnp.float32)
            s = qt @ jnp.swapaxes(kt, -1, -2) / (D ** 0.5)
            mask = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return (p @ vt).astype(jnp.float32).sum()

        emit("attn_dense_b1", _time(jax.jit(jax.grad(dense)), iters, q1))

    # full step with the r2-era UNFUSED llama layouts (fuse_attention_qkv
    # / fuse_mlp landed in 815228d, after the last good measurement) —
    # attributes the fused-matmul change. BENCH_UNFUSED=1 opts in (one
    # extra full-step compile is ~3 min of chip time).
    if os.environ.get("BENCH_UNFUSED", "0") not in ("0", "", "false"):
        emit("step_unfused", _time_full_step(
            size, S, iters, fuse_attention_qkv=False, fuse_mlp=False))

    # optimizer update alone: an AdamW-shaped tree update at the model's
    # full param count.
    # re-capture first: the TrainStep above donated (deleted) the
    # original param buffers; the model now holds the updated arrays
    params = {k: t.data for k, t in model.state_dict().items()
              if k in set(pkeys)}
    grads = {k: jnp.zeros_like(v) for k, v in params.items()}
    m = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    vv = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}

    def adamw(params, grads, m, v):
        out_p, out_m, out_v = {}, {}, {}
        for kk in params:
            g = grads[kk].astype(jnp.float32)
            m2 = 0.9 * m[kk] + 0.1 * g
            v2 = 0.999 * v[kk] + 0.001 * g * g
            p2 = params[kk].astype(jnp.float32) - 3e-4 * (
                m2 / (jnp.sqrt(v2) + 1e-8) + 0.1 * params[kk].astype(
                    jnp.float32))
            out_p[kk] = p2.astype(params[kk].dtype)
            out_m[kk], out_v[kk] = m2, v2
        return out_p, out_m, out_v

    # no donation here: a diagnostic wants repeatable calls on live
    # buffers (the real TrainStep donates; this isolates update cost)
    jad = jax.jit(adamw)
    emit("adamw", _time(jad, max(iters // 2, 1), params, grads, m, vv),
         max(iters // 2, 1))


if __name__ == "__main__":
    main()
