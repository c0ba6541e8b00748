"""paddle_tpu.observability — unified runtime telemetry.

One subsystem (see the per-module docstrings):

- `metrics`  — process-wide registry of counters/gauges/fixed-bucket
  histograms with labels; disarmed by default (single bool check per
  record site, the fault_injection.py discipline).
- `spans`    — `span(name, **attrs)` context manager: bounded in-memory
  ring + jax.profiler.TraceAnnotation forwarding (XProf correlation);
  `setup_span` / `setup_event` for what runs once per trace or compile
  (recorded armed or not, pinned in the ring).
- `scopes`   — the ONE vocabulary of the compiled train step: the
  `jax.named_scope` names on its device operations (phases `forward`,
  `backward`, `grad_sync`, `optimizer`; components `embed`, `layers`,
  `norm`, `attn/qkv`, `attn/rope`, `attn/core`, `attn/out`, `mlp`, `head`,
  `loss`; collectives `tp/all_reduce`, `tp/relayout`), TrainStep's three
  per-call host spans (`train_step.call_args`, `.dispatch`,
  `.write_back`: bare TraceAnnotations, a relaxed atomic load each when
  no profiler listens) and its set-up events (`train_step.lower` and the
  phases inside it, `train_step.trace`, `.to_mlir`, `.traced`,
  `xla.backend_compile`, `xla.to_mlir`, `xla.cache_hit/miss`). Scopes are
  metadata of the compiled program: no flag, nothing at run time. Read
  them in XProf / `paddle.profiler` (op names, host spans on one clock),
  from `spans.ring()`, or with `chipbench/run.py --trace 1`.
- `export`   — Prometheus text dump (+ optional HTTP endpoint via
  FLAGS_metrics_port), atomic JSON / append-only JSONL writers, and the
  crash flight recorder (FLAGS_flight_recorder) that leaves a
  post-mortem artifact when a trainer hangs, crashes or is killed.
- `goodput`  — the goodput ledger: step-window wall time decomposed into
  labeled productive/badput buckets + a live MFU gauge.
- `device_events` — per-execution telemetry: the jax.monitoring bridge
  (compile durations and cache events, kept as set-up events armed or
  not) + per-executable dispatch accounting keyed by a trace-time tag
  (closes the trace-time-only collective caveat).
- `federation` — per-rank snapshot publishing (FLAGS_metrics_snapshot)
  + the launch supervisor's job-level merged /metrics.
- `view`     — `python -m paddle_tpu.observability.view`: merge flight
  JSONL files across ranks/incarnations into one post-mortem timeline.

Arm everything with `FLAGS_metrics=1` (env var — read at import so
subprocess chaos tests inherit it — or paddle.set_flags) or
`observability.enable()`. Instrumented call sites live in
autograd/tape (dispatch cache, via collector), distributed/{collective,
checkpoint, elastic, _net, rpc, watchdog}, utils/fault_injection (via
collector), io/prefetch, hapi/model, jit.TrainStep, inference/serving
and profiler.Profiler.
"""
from __future__ import annotations

import os
import threading

from . import (device_events, export, goodput, metrics,  # noqa: F401
               reqtrace, scopes, spans)
from .export import (append_jsonl, flight_dump,  # noqa: F401
                     install_flight_recorder, prometheus_text,
                     serve_metrics, uninstall_flight_recorder,
                     write_snapshot)
from .metrics import counter, gauge, histogram, snapshot  # noqa: F401
from .spans import span  # noqa: F401

__all__ = ["metrics", "spans", "export", "goodput", "device_events",
           "reqtrace", "enable", "enabled", "arm", "span",
           "counter", "gauge", "histogram", "snapshot", "prometheus_text",
           "write_snapshot", "append_jsonl", "serve_metrics",
           "install_flight_recorder", "uninstall_flight_recorder",
           "flight_dump", "update_device_memory_gauges"]


def enable(on: bool = True) -> None:
    """Arm (or disarm) the metrics registry and span tracing together.
    (The jax.monitoring listeners are installed when device_events is
    imported; disarmed they keep only the set-up events.)"""
    metrics.enable(on)
    spans.enable(on)


def enabled() -> bool:
    return metrics.enabled()


_arm_lock = threading.Lock()
_arm_count = 0
_arm_prev = False


def arm():
    """Arm the registry+spans and return an idempotent restore()
    callable. REFCOUNTED: with two overlapping armers (a Profiler
    running across a Model.fit that carries a MetricsCallback), the
    first restore() must not disarm telemetry out from under the one
    still active — only the last restore standing reverts to the state
    captured before the first arm. The one implementation of the
    protocol, so Profiler and MetricsCallback cannot diverge."""
    global _arm_count, _arm_prev
    with _arm_lock:
        if _arm_count == 0:
            _arm_prev = metrics.enabled()
        if not metrics.enabled():
            enable(True)    # also re-arms after a direct enable(False)
        _arm_count += 1
    done = [False]

    def restore():
        global _arm_count
        with _arm_lock:
            if done[0]:
                return
            done[0] = True
            _arm_count -= 1
            if _arm_count == 0 and not _arm_prev:
                enable(False)

    return restore


# device-memory gauges (Profiler.step); created
# here once — consumers import the helper, not their own instruments
_G_MEM_IN_USE = metrics.gauge("device.bytes_in_use",
                              "device memory currently allocated (bytes); "
                              "unlabeled cell = host total, device=... "
                              "cells = per chip")
_G_MEM_PEAK = metrics.gauge("device.peak_bytes_in_use",
                            "peak device memory allocated (bytes); "
                            "unlabeled cell = host total, device=... "
                            "cells = per chip")


def update_device_memory_gauges():
    """Refresh device.bytes_in_use / device.peak_bytes_in_use from EVERY
    local device's memory_stats(): per-device labeled cells
    (device="tpu:0", ...) plus the unlabeled host-total cell — a
    multi-chip host no longer reports device 0 as the whole host.
    Returns {'bytes_in_use', 'peak_bytes_in_use', 'per_device'} (totals
    + the per-device map) — or None on backends without memory_stats
    (a clean no-op; CPU jaxlib returns None)."""
    try:
        import jax
        devs = jax.local_devices()
    except Exception:
        return None
    total_in = total_peak = 0
    per_device = {}
    for d in devs:
        try:
            st = d.memory_stats()
        except Exception:
            st = None
        if not st:
            continue
        in_use = int(st.get("bytes_in_use", 0))
        peak = int(st.get("peak_bytes_in_use", in_use))
        label = f"{d.platform}:{d.id}"
        per_device[label] = {"bytes_in_use": in_use,
                             "peak_bytes_in_use": peak}
        _G_MEM_IN_USE.set(in_use, device=label)
        _G_MEM_PEAK.set(peak, device=label)
        total_in += in_use
        total_peak += peak
    if not per_device:
        return None
    _G_MEM_IN_USE.set(total_in)
    _G_MEM_PEAK.set(total_peak)
    return {"bytes_in_use": total_in, "peak_bytes_in_use": total_peak,
            "per_device": per_device}


# env arming at import (the fault_injection.py pattern): subprocess chaos
# tests set these before the interpreter starts; paddle.set_flags routes
# here in-process (framework/core._apply_flag)
_FALSY_ENV = (None, "", "0", "false", "False", "off", "OFF")
if os.environ.get("FLAGS_metrics") not in _FALSY_ENV:
    enable(True)
if os.environ.get("FLAGS_span_ring_size"):
    try:
        spans.set_ring_size(int(os.environ["FLAGS_span_ring_size"]))
    except ValueError:
        pass
if os.environ.get("FLAGS_metrics_port"):
    try:
        export.serve_metrics(int(os.environ["FLAGS_metrics_port"]))
    except (ValueError, OSError):
        pass        # bad/busy port must not break `import paddle_tpu`
_flight_path = os.environ.get("FLAGS_flight_recorder")
if _flight_path:
    try:
        install_flight_recorder(_flight_path)
    except OSError:
        pass    # unwritable path must not break `import paddle_tpu`
_snapshot_path = os.environ.get("FLAGS_metrics_snapshot")
if _snapshot_path:
    try:
        from . import federation as _federation
        _federation.start_publisher(_snapshot_path)
    except Exception:
        pass    # unwritable path must not break `import paddle_tpu`
if os.environ.get("FLAGS_lock_witness") not in _FALSY_ENV:
    from . import lockwitness as _lockwitness
    _lockwitness.enable(True)
_trace_sink_path = os.environ.get("FLAGS_request_trace_sink")
if _trace_sink_path:
    try:
        reqtrace.set_sink(_trace_sink_path)
    except OSError:
        pass    # unwritable path must not break `import paddle_tpu`
