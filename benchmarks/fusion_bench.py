#!/usr/bin/env python
"""Fused-transformer benchmark + equivalence gate (ISSUE 20): the
FLAGS_fused_transformer hot path (fused residual+RMSNorm, blockwise
SwiGLU, fused QKV+RoPE prologue) vs the kill-switch-off unfused path.

Runs the SAME llama_tiny training job (f32, scan_layers + remat, the
default save_matmul_outputs remat policy) twice:

  (a) fused       — FLAGS_fused_transformer=1 (the default);
  (b) kill switch — FLAGS_fused_transformer=0, today's unfused path.

and one greedy KV-cache generation per configuration.

Guards (exit 1 on violation — CI regression gate):
  * LOSS TRAJECTORY: max per-step |fused - off| deviation over STEPS
    steps <= LOSS_TOL (1e-6) — the two tapes must agree to float order
    (on CPU the kernels' jnp fallbacks make them bitwise; on TPU the
    Pallas routes may differ in the last ulp).
  * KILL SWITCH: (b) must reproduce the pre-fusion path — and the
    greedy serving tokens of (a) and (b) must be IDENTICAL.
  * FINAL WEIGHTS: bitwise on CPU (fallback routes), reported always.

tokens/s + the goodput ledger decomposition (extra.goodput, same shape
bench.py emits) are recorded for both configurations; the fused/off
tokens-per-second ratio lands in BENCH_TREND as
fused_transformer_speedup@<device>. A CPU run says nothing about the
Pallas routes: no device number comes from this script.

Run: JAX_PLATFORMS=cpu python benchmarks/fusion_bench.py
Artifact: benchmarks/FUSION_BENCH.json (+ the trend series entry)
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.optimizer as opt  # noqa: E402
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny  # noqa: E402

LOSS_TOL = float(os.environ.get("BENCH_FUSION_LOSS_TOL", "1e-6"))
STEPS = int(os.environ.get("BENCH_STEPS", "40"))
BATCH = int(os.environ.get("BENCH_BATCH", "4"))
SEQ = int(os.environ.get("BENCH_SEQ", "64"))
GEN_TOKENS = int(os.environ.get("BENCH_GEN_TOKENS", "16"))


def _build():
    paddle.seed(0)
    cfg = llama_tiny(dtype="float32")
    m = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    return m, o


def _run(flag, steps=STEPS):
    """Train `steps` steps under FLAGS_fused_transformer=flag; return
    (losses, tokens_per_s, goodput, final_weights, greedy_tokens)."""
    from paddle_tpu import observability as _obs
    from paddle_tpu.observability import goodput as _goodput

    paddle.set_flags({"FLAGS_fused_transformer": flag})
    m, o = _build()
    ts = paddle.jit.TrainStep(m, o, lambda ids, lb: m.loss(ids, lb))
    rng = np.random.RandomState(7)
    ids = paddle.to_tensor(
        rng.randint(0, 1024, (BATCH, SEQ)).astype(np.int64))

    losses = [float(ts(ids, ids).numpy())]       # step 1 includes compile
    restore = _obs.arm()
    loss = ts(ids, ids)                          # armed warmup (MFU gauge)
    losses.append(float(loss.numpy()))
    _goodput.reset()
    _goodput.open_window()
    t0 = time.perf_counter()
    for _ in range(steps - 2):
        loss = ts(ids, ids)
        losses.append(float(loss.numpy()))
    dt = time.perf_counter() - t0
    _goodput.step_boundary()
    gp = _goodput.summary()
    restore()
    tok_s = (steps - 2) * BATCH * SEQ / dt if dt else 0.0
    goodput = {
        "productive_seconds": round(gp["productive_seconds"], 4),
        "badput_seconds": {k: round(v, 4)
                           for k, v in gp["badput_seconds"].items()},
        "productive_fraction": round(gp["productive_fraction"], 4),
        "attributed_fraction": round(gp["wall_seconds"] / dt, 4)
                               if dt else 0.0,
        "mfu": round(gp["mfu"], 4),
    }
    weights = {k: np.asarray(t.data) for k, t in m.state_dict().items()}
    toks = np.asarray(m.generate(
        paddle.to_tensor(rng.randint(0, 1024, (2, 12)).astype(np.int64)),
        max_new_tokens=GEN_TOKENS).data)
    return losses, tok_s, goodput, weights, toks


def _append_trend(value):
    """One fused_transformer_speedup@<device> point in the cross-round
    series: atomic tmp+replace, series capped at 50."""
    trend_p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_TREND.json")
    try:
        with open(trend_p) as f:
            trend = json.load(f)
    except (OSError, ValueError):
        trend = {}
    device = jax.devices()[0].platform
    series = trend.setdefault(f"fused_transformer_speedup@{device}", [])
    series.append({
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "value": round(value, 4),
        "unit": "x_tokens_per_s_vs_unfused",
        "device": device,
    })
    del series[:-50]
    try:
        tmp = trend_p + ".tmp"
        with open(tmp, "w") as f:
            json.dump(trend, f, indent=1)
        os.replace(tmp, trend_p)
    except OSError:
        pass


def main():
    fused_losses, fused_tok, fused_gp, fused_w, fused_toks = _run(1)
    off_losses, off_tok, off_gp, off_w, off_toks = _run(0)

    dev = [abs(a - b) for a, b in zip(fused_losses, off_losses)]
    traj_ok = max(dev) <= LOSS_TOL
    tokens_ok = np.array_equal(fused_toks, off_toks)
    weights_bitwise = all(np.array_equal(fused_w[k], off_w[k])
                          for k in fused_w)
    speedup = fused_tok / off_tok if off_tok else 0.0

    report = {
        "bench": "fused_transformer",
        "device": jax.devices()[0].platform,
        "steps": STEPS,
        "batch_seq": [BATCH, SEQ],
        "loss_tol": LOSS_TOL,
        "max_trajectory_deviation": max(dev),
        "trajectory_guard_passed": bool(traj_ok),
        "greedy_tokens_identical": bool(tokens_ok),
        "final_weights_bitwise": bool(weights_bitwise),
        "final_loss": {"fused": fused_losses[-1], "off": off_losses[-1]},
        "tokens_per_s": {"fused": round(fused_tok, 1),
                         "off": round(off_tok, 1)},
        "fused_speedup_x": round(speedup, 4),
        "extra": {"goodput": {"fused": fused_gp, "off": off_gp}},
        "note": ("wall times on CPU measure XLA dispatch through the jnp "
                 "fallbacks, not the Pallas routes: not a device number"),
    }
    print(json.dumps(report, indent=2))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "FUSION_BENCH.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)

    ok = traj_ok and tokens_ok
    if ok:
        _append_trend(speedup)
    else:
        print(f"FAIL: trajectory={traj_ok} (max dev {max(dev):g} vs "
              f"{LOSS_TOL:g}) greedy_tokens_identical={tokens_ok}",
              file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
