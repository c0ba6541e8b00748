#!/usr/bin/env python
"""ZeRO sharded-optimizer benchmark (ISSUE 16): per-rank optimizer-state
memory + convergence of ShardingPlan(zero=2) vs the replicated update.

Runs the SAME data-parallel training job on a dp=8 mesh (8 forced host
devices on CPU; real chips on TPU) in three configurations:

  (a) replicated — ShardingPlan without zero: full f32 accumulator
      state on every rank, gradients via the GSPMD all-reduce;
  (b) zero=2     — ShardingPlan(zero=2): reduce-scatter grads, update
      each rank's flat 1/nranks shard of params with shard-shaped
      accumulator state, all-gather params back (arxiv 2004.13336);
  (c) kill switch — the SAME zero=2 plan under FLAGS_zero=0, which must
      compile the exact pre-ZeRO replicated path.

Guards (exit 1 on violation — CI regression gate):
  * MEMORY: per-rank optimizer-state bytes of (b), from
    TrainStep.opt_state_bytes_per_rank(), must be <= MAX_STATE_FRACTION
    (1.6/nranks) of the replicated run's — i.e. >= nranks/1.6 = 5x
    smaller at dp=8 (the slack covers flat-layout tail padding).
  * CONVERGENCE: step-0 loss of (b) identical to (a) within float-order
    tolerance; per-step trajectory within LOSS_TOL_REL (3%).
  * KILL SWITCH: (c) must match (a) BITWISE — identical losses and
    final weights, not merely close.

The quantized-wire composition (zero=2 + grad_sync="int8" + error
feedback) is exercised and reported (trajectory deviation) but its wire
ratio is owned by quant_collective_bench.py.

Run: JAX_PLATFORMS=cpu python benchmarks/zero_bench.py
Artifact: benchmarks/ZERO_BENCH.json (+ a zero_opt_state_reduction
series entry in benchmarks/BENCH_TREND.json)
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
from paddle_tpu.distributed.sharding import ShardingPlan  # noqa: E402

LOSS_TOL_REL = float(os.environ.get("BENCH_LOSS_TOL_REL", "0.03"))
# per-rank state-bytes ceiling as a fraction of replicated: 1.6/nranks
# leaves room for the shard_sizes tail padding on small tensors
MAX_STATE_FRACTION = float(
    os.environ.get("BENCH_MAX_STATE_FRACTION", str(1.6 / 8)))
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


def _run(zero=0, grad_sync=None, flag=1, steps=STEPS):
    from jax.sharding import Mesh
    paddle.set_flags({"FLAGS_zero": flag})
    mesh = Mesh(np.asarray(jax.devices()[:N_DP]).reshape(N_DP), ("dp",))
    m, o = _build()
    plan = ShardingPlan(mesh, zero=zero, grad_sync=grad_sync,
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
    weights = {k: np.asarray(t.data) for k, t in m.state_dict().items()}
    return losses, wall, ts.opt_state_bytes_per_rank(), weights


def _append_trend(value):
    """One zero_opt_state_reduction@<device> point in the cross-round
    series (same shape bench.py's _attach_trend writes): atomic
    tmp+replace, series capped at 50."""
    trend_p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_TREND.json")
    try:
        with open(trend_p) as f:
            trend = json.load(f)
    except (OSError, ValueError):
        trend = {}
    device = jax.devices()[0].platform
    series = trend.setdefault(f"zero_opt_state_reduction@{device}", [])
    series.append({
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "value": round(value, 4),
        "unit": "x_smaller_per_rank",
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
    paddle.set_flags({"FLAGS_quant_collectives": 1,
                      "FLAGS_quant_collectives_block": BLOCK})
    ref_losses, ref_wall, ref_bytes, ref_w = _run(zero=0)
    z_losses, z_wall, z_bytes, _ = _run(zero=2)
    off_losses, _, _, off_w = _run(zero=2, flag=0)
    q_losses, q_wall, _, _ = _run(zero=2, grad_sync="int8")

    reduction = ref_bytes / max(z_bytes, 1)
    mem_ok = z_bytes <= MAX_STATE_FRACTION * ref_bytes

    dev = [abs(a - b) for a, b in zip(ref_losses, z_losses)]
    step0_same = abs(z_losses[0] - ref_losses[0]) <= \
        1e-5 * max(abs(ref_losses[0]), 1.0)
    converged = (step0_same
                 and abs(z_losses[-1] - ref_losses[-1])
                 <= max(LOSS_TOL_REL * abs(ref_losses[-1]), 1e-3)
                 and max(dev) <= max(LOSS_TOL_REL * max(ref_losses), 5e-3))

    kill_bitwise = (off_losses == ref_losses
                    and all(np.array_equal(ref_w[k], off_w[k])
                            for k in ref_w))

    q_dev = [abs(a - b) for a, b in zip(ref_losses, q_losses)]
    q_converged = max(q_dev) <= max(LOSS_TOL_REL * max(ref_losses), 5e-3)

    report = {
        "bench": "zero_sharded_update",
        "device": jax.devices()[0].platform,
        "world": N_DP,
        "steps": STEPS,
        "opt_state_bytes_per_rank": {
            "replicated": ref_bytes, "zero2": z_bytes},
        "opt_state_reduction_x": round(reduction, 4),
        "max_state_fraction": MAX_STATE_FRACTION,
        "memory_guard_passed": bool(mem_ok),
        "final_loss_replicated": ref_losses[-1],
        "final_loss_zero2": z_losses[-1],
        "max_trajectory_deviation": max(dev),
        "convergence_guard_passed": bool(converged),
        "kill_switch_bitwise": bool(kill_bitwise),
        "int8_ef_composed_max_deviation": max(q_dev),
        "int8_ef_composed_converged": bool(q_converged),
        "step_wall_ms": {
            "replicated": round(ref_wall * 1e3, 3),
            "zero2": round(z_wall * 1e3, 3),
            "zero2_int8_ef": round(q_wall * 1e3, 3),
        },
        "note": ("wall times on CPU measure XLA dispatch, not HBM/ICI; "
                 "not a device number"),
    }
    print(json.dumps(report, indent=2))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "ZERO_BENCH.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)

    ok = mem_ok and converged and kill_bitwise and q_converged
    if ok:
        _append_trend(reduction)
    else:
        print(f"FAIL: mem_ok={mem_ok} (bytes {z_bytes} vs "
              f"{MAX_STATE_FRACTION:.3f}*{ref_bytes}) converged={converged} "
              f"kill_bitwise={kill_bitwise} int8_ef={q_converged}",
              file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
