"""What every driver shares: the run's context, the device trace, the
peak-memory reading, the kernel check, the compared-number rows."""
from __future__ import annotations

import contextlib
import gc
import os
import re
import shutil
import time
from dataclasses import dataclass


@dataclass
class Ctx:
    root: str                 # the checkout
    workload: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    config: dict              # the configuration file
    traffic: dict             # the traffic mix's file
    cell: dict                # the cell's file
    peaks: dict               # this device_kind's row of peaks.json
    devices: list
    on_chip: bool             # False only under the CPU tests
    t_start: float            # process start, perf_counter clock


def compared(name, value, limit, note=""):
    """One number `correct` rests on, beside its limit (value <= limit)."""
    ok = bool(value <= limit)          # NaN compares False: not correct
    return {"name": name, "value": value, "limit": limit, "ok": ok,
            "note": note}


def kernels_in(compiled_text):
    """Names of every Mosaic kernel in a compiled program's text: the
    HLO instruction's own name (`splash_mqa_fwd_residuals.1`: what the
    device trace calls it) and, where the line carries one, its op_name
    (`jit(pure)/.../swiglu_fwd/pallas_call`)."""
    names = []
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if m:
            names.append(m.group(1))
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            names.append(m.group(1))
    return names


def require_kernels(compiled_text, wanted, where):
    names = kernels_in(compiled_text)
    missing = [k for k, marks in wanted.items()
               if not any(m in n for n in names for m in marks)]
    if missing:
        raise AssertionError(
            f"{where}: compiled program holds no {missing} kernel "
            f"(tpu_custom_calls found: {sorted(set(names))})")
    return len(names)


def release():
    """Give freed state's device memory back: what only reference cycles
    or jit's caches still hold."""
    import jax
    jax.clear_caches()
    gc.collect()


def peak_bytes(devices):
    """Peak bytes in use on the fullest device, or None where the
    backend keeps no count (CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def percentile(values, q):
    """q-th percentile (0-100), linear between order statistics."""
    import numpy as np
    if not len(values):
        raise ValueError("percentile of nothing")
    return float(np.percentile(values, q))


@contextlib.contextmanager
def device_trace(ctx):
    """Profile what runs inside: yields a dict that holds the trace's
    directory and the traced wall seconds once the block has ended. The
    directory is a fixed place inside the checkout."""
    import jax
    path = os.path.join(ctx.root, ".chipbench_trace", ctx.workload)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    out = {"dir": path}
    # host spans (TraceAnnotation) on, the Python call tracer off: it
    # would record every call of the engine's scheduler and slow the
    # host loop the trace is there to time
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        yield out
    finally:
        out["wall_s"] = time.perf_counter() - t0
        jax.profiler.stop_trace()


def plan_shardings(plan, model, shapes):
    """{name: NamedSharding} the plan gives each parameter, so that the
    weights are made where they will live."""
    from jax.sharding import NamedSharding
    plan.attach_model(model)
    return {name: NamedSharding(plan.mesh, plan.param_spec(name, s))
            for name, s in shapes.items()}
