#!/usr/bin/env python
"""Quantized-collectives benchmark (ISSUE 8): wire bytes + convergence
of the int8 blockwise gradient sync vs the fp32 GSPMD psum baseline.

Runs the SAME data-parallel training job twice on a dp=8 mesh (8 forced
host devices on CPU; real chips on TPU):

  (a) fp32 sync  — ShardingPlan without grad_sync: gradients reduced by
      the implicit GSPMD all-reduce, today's default path;
  (b) quantized  — ShardingPlan(grad_sync="int8",
      grad_sync_error_feedback=True): the EQuARX two-phase chain
      (blockwise absmax quantize -> reduce_scatter int8 payloads +
      per-block f32 scales -> fp32 accumulate -> re-quantize ->
      all_gather) behind collective.grad_sync_all_reduce.

Guards (exit 1 on violation — CI regression gate):
  * WIRE ratio >= MIN_WIRE_RATIO (3.5x): quantized wire bytes (from the
    collective.wire_bytes_total counter, padding included) vs the SAME
    reduce_scatter+all_gather decomposition carried in fp32 — the
    physical compression, 4 / (1 + 4/block) asymptotically. The naive
    payload-entering ratio (collective.bytes_total / wire) is reported
    too; it under-counts the fp32 side (one phase) so it reads lower.
  * convergence: per-step loss trajectories must agree within
    LOSS_TOL_REL of the fp32 run (identical step 0 — quantization only
    touches gradients), and the final losses must be close.

Also emits a grad-sync wall-time line per configuration (per-step ms);
on the CPU container this measures XLA overhead, not ICI — the number
that matters is an on-chip run.

Run: JAX_PLATFORMS=cpu python benchmarks/quant_collective_bench.py
Artifact: benchmarks/QUANT_COLLECTIVE_BENCH.json
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
import paddle_tpu.optimizer as opt  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.distributed.sharding import ShardingPlan  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.quantization import comm as qcomm  # noqa: E402

MIN_WIRE_RATIO = float(os.environ.get("BENCH_MIN_WIRE_RATIO", "3.5"))
LOSS_TOL_REL = float(os.environ.get("BENCH_LOSS_TOL_REL", "0.03"))
STEPS = int(os.environ.get("BENCH_STEPS", "40"))
BATCH = int(os.environ.get("BENCH_BATCH", "64"))
D_IN, D_HID, D_OUT = 256, 1024, 10
N_DP = 8
BLOCK = 256


def _build():
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(D_IN, D_HID), nn.ReLU(),
                      nn.Linear(D_HID, D_HID // 2), nn.ReLU(),
                      nn.Linear(D_HID // 2, D_OUT))
    o = opt.AdamW(learning_rate=0.003, parameters=m.parameters())
    return m, o


def _run(grad_sync, steps=STEPS):
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:N_DP]).reshape(N_DP), ("dp",))
    m, o = _build()
    plan = ShardingPlan(mesh, grad_sync=grad_sync,
                        grad_sync_error_feedback=bool(grad_sync))
    rng = np.random.RandomState(7)
    x = rng.randn(BATCH, D_IN).astype(np.float32)
    w_true = rng.randn(D_IN, D_OUT).astype(np.float32) / np.sqrt(D_IN)
    y = (x @ w_true).astype(np.float32)

    def step_fn(xb, yb):
        return F.mse_loss(m(xb), yb)

    ts = paddle.jit.TrainStep(m, o, step_fn, shard=plan)
    xb, yb = paddle.to_tensor(x), paddle.to_tensor(y)
    losses = [float(ts(xb, yb).numpy())]        # step 1 includes compile
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        losses.append(float(ts(xb, yb).numpy()))
    wall = (time.perf_counter() - t0) / max(steps - 1, 1)
    params, _ = paddle.jit.capture_state(m)
    return losses, wall, params


def _fp32_equiv_wire(params, block=BLOCK, n=N_DP):
    """Wire bytes the SAME reduce_scatter+all_gather decomposition
    (padding included) would carry in fp32 — the apples-to-apples
    denominator for the compression ratio."""
    total = 0
    for v in params.values():
        s, padded = qcomm.shard_sizes(int(v.size), n, block)
        total += (padded + s) * 4
    return total


def main():
    paddle.set_flags({"FLAGS_quant_collectives": 1,
                      "FLAGS_quant_collectives_block": BLOCK})
    fp_losses, fp_wall, _ = _run(None)

    obs.enable(True)          # armed BEFORE the quantized compile: the
    try:                      # shard_map chain's counters are trace-time
        q_losses, q_wall, q_params = _run("int8")
        snap = metrics.snapshot()
        wire = snap["counters"]["collective.wire_bytes_total"]["op=grad_sync"]
        payload = snap["counters"]["collective.bytes_total"]["op=grad_sync"]
    finally:
        obs.enable(False)

    fp_equiv = _fp32_equiv_wire(q_params)
    wire_ratio = fp_equiv / wire
    payload_ratio = payload / wire

    dev = [abs(a - b) for a, b in zip(fp_losses, q_losses)]
    tol = max(LOSS_TOL_REL * abs(fp_losses[-1]), 1e-3)
    # step 0: quantization only touches gradients, but the two
    # compilations reduce the loss in different float orders (GSPMD
    # global mean vs per-shard mean + pmean) — near-equal, not bitwise
    step0_same = abs(q_losses[0] - fp_losses[0]) <= \
        1e-5 * max(abs(fp_losses[0]), 1.0)
    converged = (step0_same
                 and abs(q_losses[-1] - fp_losses[-1]) <= tol
                 and max(dev) <= max(LOSS_TOL_REL * max(fp_losses), 5e-3))

    report = {
        "bench": "quant_collective",
        "device": jax.devices()[0].platform,
        "world": N_DP,
        "block": BLOCK,
        "steps": STEPS,
        "wire_ratio_vs_fp32_same_decomposition": round(wire_ratio, 4),
        "payload_entering_ratio": round(payload_ratio, 4),
        "wire_bytes_per_sync": wire,
        "fp32_equiv_wire_bytes": fp_equiv,
        "min_wire_ratio": MIN_WIRE_RATIO,
        "final_loss_fp32_sync": fp_losses[-1],
        "final_loss_quantized": q_losses[-1],
        "max_trajectory_deviation": max(dev),
        "loss_tolerance": tol,
        "convergence_guard_passed": bool(converged),
        "grad_sync_wall_ms_per_step": {
            "fp32_sync": round(fp_wall * 1e3, 3),
            "quantized": round(q_wall * 1e3, 3),
        },
        "note": ("wall times on CPU measure XLA dispatch, not ICI; "
                 "not a device number"),
    }
    print(json.dumps(report, indent=2))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "QUANT_COLLECTIVE_BENCH.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)

    ok = wire_ratio >= MIN_WIRE_RATIO and converged
    if not ok:
        print(f"FAIL: wire_ratio={wire_ratio:.3f} (need >= "
              f"{MIN_WIRE_RATIO}) converged={converged}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
