"""Goodput ledger: step-time decomposition into labeled buckets + a live
MFU gauge (the attribution layer the ROADMAP MFU-recovery campaign is
blocked on — 0.27-0.33 MFU says the gap exists, this says WHERE the
wall-clock goes; measurement frame per the Gemma-on-TPU serving
comparison, PAPERS.md arxiv 2605.25645).

Model: the training loop's wall time is a sequence of step WINDOWS —
`step_boundary()` is called once per step (jit.TrainStep does this; any
custom loop may too) and closes the window opened by the previous
boundary (or by an explicit `open_window()` at loop start). Inside a
window, instrumented subsystems attribute badput seconds to a category:

  data_wait        consumer blocked on the input pipeline — fed from the
                   DevicePrefetcher starved/warmup seam (io/prefetch.py)
                   and from `timed_iter` wrapping the hapi fit loop
  host_pull        blocking jax.device_get syncs (hapi.model._host_pull)
  compile          XLA compilation, via the jax.monitoring duration-event
                   listener (observability/device_events.py)
  checkpoint_stall trainer blocked on a synchronous checkpoint commit
  elastic_barrier  recovery/health barrier waits (distributed/elastic)
  elastic_recovery checkpoint restore + replay after a world change

Whatever remains of the window is PRODUCTIVE device-execute time:

  productive = max(0, wall - sum(badput))        [category=device_execute]

so the bucket seconds sum to the measured wall time by construction and
roll into `goodput.productive_seconds_total` / `goodput.badput_seconds_total`
counters. The live MFU gauge divides the executable's own
`lowered.cost_analysis()` FLOPs (the seam
distributed/auto_parallel/cost_model.py already reads) by
step-seconds * peak FLOP/s of the local chip.

Disarmed (the registry discipline): `attribute()` / `step_boundary()` are
a single module-global bool check — the hot-path overhead guard in
tests/test_goodput.py holds the line.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from . import metrics as _m

__all__ = ["attribute", "time_section", "timed_iter", "consumer_wait",
           "open_window", "step_boundary", "summary", "reset",
           "peak_flops_per_sec", "CATEGORIES"]

CATEGORIES = ("data_wait", "host_pull", "compile", "checkpoint_stall",
              "elastic_barrier", "elastic_recovery", "other")

_C_PRODUCTIVE = _m.counter(
    "goodput.productive_seconds_total",
    "step-window seconds left after badput attribution "
    "(category=device_execute)")
_C_BADPUT = _m.counter(
    "goodput.badput_seconds_total",
    "step-window seconds attributed to a non-productive category")
_C_STEPS = _m.counter("goodput.steps_total",
                      "step windows closed by the ledger")
_G_MFU = _m.gauge(
    "goodput.mfu", "live model FLOPs utilization: executable FLOPs / "
    "(step seconds * peak FLOP/s); 0 when peak is unknown")
_G_STEP_FLOPS = _m.gauge(
    "goodput.step_flops",
    "XLA cost_analysis FLOPs of the compiled step feeding the MFU gauge")
_G_LAST_STEP = _m.gauge("goodput.last_step_seconds",
                        "wall seconds of the last closed step window")

_lock = threading.RLock()
_t0: Optional[float] = None              # open-window start
_window_attr: Dict[str, float] = {}      # category -> seconds this window
_totals: Dict[str, float] = {}           # category -> seconds since reset
_productive_total = 0.0
_steps = 0
_last_mfu = 0.0

# thread-local guard: while `timed_iter` is timing a consumer-side
# `next()`, the DevicePrefetcher's starved/warmup attribution for the
# same wait must not double-count (the q.get block happens INSIDE that
# next() on the same thread)
_tl = threading.local()


def attribute(category: str, seconds: float) -> None:
    """Attribute `seconds` of the current step window to a badput
    category. Disarmed: one bool check."""
    if not _m.enabled():
        return
    if seconds <= 0:
        return
    with _lock:
        _window_attr[category] = _window_attr.get(category, 0.0) + seconds


class time_section:
    """`with time_section("checkpoint_stall"): ...` — attribute the block's
    wall time. Disarmed: an object allocation + one bool check."""

    __slots__ = ("category", "_t0")

    def __init__(self, category: str):
        self.category = category

    def __enter__(self):
        self._t0 = time.perf_counter() if _m.enabled() else None
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            attribute(self.category, time.perf_counter() - self._t0)
        return False


def timed_iter(iterable, category: str = "data_wait"):
    """Wrap an iterable so time the consumer spends blocked in `next()`
    is attributed to `category` (hapi fit wraps its loader with this).
    Sets the dedup guard so the DevicePrefetcher's starved/warmup seam
    does not attribute the same wait twice."""
    it = iter(iterable)
    while True:
        t0 = time.perf_counter()
        _tl.timing = True
        try:
            item = next(it)
        except StopIteration:
            return
        finally:
            _tl.timing = False
        attribute(category, time.perf_counter() - t0)
        yield item


def consumer_wait(seconds: float) -> None:
    """The DevicePrefetcher starved/warmup seam: attribute a staged-batch
    queue wait as data_wait UNLESS a `timed_iter` on this thread is
    already timing the enclosing next() (hapi fit path)."""
    if getattr(_tl, "timing", False):
        return
    attribute("data_wait", seconds)


def open_window() -> None:
    """Start (or restart) a step window NOW, discarding attribution that
    accumulated outside any window. Called at loop start so the first
    step's window covers its data wait and compile."""
    global _t0
    if not _m.enabled():
        return
    with _lock:
        _window_attr.clear()
        _t0 = time.perf_counter()


def step_boundary(flops: Optional[float] = None) -> Optional[dict]:
    """Close the current step window and open the next one. Returns the
    window's breakdown {wall, productive, badput: {category: s}} — or
    None when disarmed or no window was open (first boundary just opens
    one). `flops` (the executable's cost_analysis count) drives the MFU
    gauge."""
    global _t0, _productive_total, _steps, _last_mfu
    if not _m.enabled():
        return None
    now = time.perf_counter()
    with _lock:
        if _t0 is None:
            _window_attr.clear()
            _t0 = now
            return None
        wall = now - _t0
        attrs = dict(_window_attr)
        _window_attr.clear()
        _t0 = now
        badput = sum(attrs.values())
        productive = max(0.0, wall - badput)
        for cat, s in attrs.items():
            _totals[cat] = _totals.get(cat, 0.0) + s
        _productive_total += productive
        _steps += 1
    _C_PRODUCTIVE.inc(productive, category="device_execute")
    for cat, s in attrs.items():
        _C_BADPUT.inc(s, category=cat)
    _C_STEPS.inc()
    _G_LAST_STEP.set(wall)
    mfu = 0.0
    if flops:
        _G_STEP_FLOPS.set(float(flops))
        peak = peak_flops_per_sec()
        if peak and wall > 0:
            mfu = float(flops) / (wall * peak)
            _G_MFU.set(mfu)
            # only a flops-carrying boundary updates the summary's MFU:
            # auxiliary windows (bench's drain window, manual
            # boundaries) must not zero the last real reading
            with _lock:
                _last_mfu = mfu
    return {"wall": wall, "productive": productive, "badput": attrs,
            "mfu": mfu}


def summary() -> dict:
    """Cumulative ledger view since reset(): step count, productive and
    per-category badput seconds, the attributed fraction of total window
    wall, and the last MFU reading."""
    with _lock:
        badput = dict(_totals)
        productive = _productive_total
        steps = _steps
        mfu = _last_mfu
    wall = productive + sum(badput.values())
    return {
        "steps": steps,
        "wall_seconds": wall,
        "productive_seconds": productive,
        "badput_seconds": badput,
        "productive_fraction": (productive / wall) if wall else 0.0,
        "mfu": mfu,
    }


def reset() -> None:
    """Drop window state and cumulative totals (registry counters are
    reset separately via metrics.reset())."""
    global _t0, _productive_total, _steps, _last_mfu
    with _lock:
        _t0 = None
        _window_attr.clear()
        _totals.clear()
        _productive_total = 0.0
        _steps = 0
        _last_mfu = 0.0


# bf16 peak FLOP/s per chip by TPU generation (Google Cloud TPU
# documentation, one page per generation) — the one table of the repo.
# Matched as a substring of the normalized
# device_kind, most specific tag first. A TPU that is not listed has NO
# peak: nothing is assumed for it.
_PEAK = {
    "v3": 123e12,
    "v4": 275e12,
    "v5litepod": 197e12, "v5lite": 197e12, "v5e": 197e12,
    "v6e": 918e12, "trillium": 918e12,
    "v5p": 459e12, "v5": 459e12,     # a v5p reports "TPU v5"
}

_peak_cache: Optional[float] = None


def peak_for_device_kind(device_kind: str) -> Optional[float]:
    """The table's bf16 peak FLOP/s for a `device.device_kind`, or None
    where the table does not know the device."""
    kind = device_kind.lower().replace(" ", "")
    for tag, peak in _PEAK.items():
        if tag in kind:
            return peak
    return None


def peak_flops_per_sec() -> float:
    """Peak FLOP/s of the local chip for the MFU gauge.
    PADDLE_PEAK_FLOPS overrides (tests, unlisted hardware); 0.0 on a
    device the table does not list (a CPU, or a TPU generation nobody
    entered) — the gauge then stays unset, and an unlisted TPU is named
    once on stderr so that a missing MFU is not a mystery."""
    global _peak_cache
    env = os.environ.get("PADDLE_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if _peak_cache is not None:
        return _peak_cache
    import jax
    d = jax.local_devices()[0]
    peak = peak_for_device_kind(getattr(d, "device_kind", ""))
    if peak is None and d.platform == "tpu":
        import sys
        print(f"goodput: no peak FLOP/s is listed for TPU device_kind "
              f"{d.device_kind!r}; MFU is not reported (add it to "
              f"observability/goodput.py _PEAK or set PADDLE_PEAK_FLOPS)",
              file=sys.stderr)
    _peak_cache = peak or 0.0
    return _peak_cache
