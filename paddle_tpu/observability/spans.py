"""Span tracing: begin/end/duration records in a bounded in-memory ring,
forwarded to jax.profiler.TraceAnnotation so user spans, checkpoint
phases and collective calls show up in XProf with no extra code
(ref: python/paddle/profiler RecordEvent; fluid/platform/profiler host
tracer events).

Armed/disarmed follows the metrics registry's discipline: a disarmed
`span(...)` is an object allocation + one bool check, nothing else — no
ring append, no TraceAnnotation, no sink calls. Arm via FLAGS_metrics /
`observability.enable()`.

Every armed span begin/end event also fans out to registered SINKS —
the crash flight recorder (observability/export.py) registers one to
write-through each event to an append-only JSONL file, which is what
lets a SIGKILLed trainer leave a post-mortem artifact naming the span
that was open at death (the begin line is on disk; the end line never
happens).

SET-UP events are the exception to armed/disarmed: `setup_span(...)` and
`setup_event(...)` sit where the program traces, lowers or compiles —
once per executable, never per step — so they record whether or not the
registry is armed, and into a pinned part of the ring that armed
per-call spans cannot evict. They are what says where the seconds before
a job's first step went (`TrainStep.lower` and its phases, every backend
compile and persistent-cache hit with the set-up span open around it);
`ring()` returns them first, each marked `"setup": True`.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Callable, Dict, List

__all__ = ["span", "setup_span", "setup_event", "enable",
           "enabled", "ring", "clear", "set_ring_size", "open_spans",
           "add_sink", "remove_sink"]

_enabled = False
_DEFAULT_RING = 512

# RLock: the flight recorder's signal-handler dump reads ring()/
# open_spans() and may interrupt a record call on the SAME (main)
# thread mid-hold — a plain Lock would deadlock the dying process
_lock = threading.RLock()
_ring: deque = deque(maxlen=_DEFAULT_RING)
# set-up events (once per trace / compile): pinned, bounded on their own
_pinned: deque = deque(maxlen=2048)
_tl = threading.local()          # .setup: set-up spans open on this thread
_seq = itertools.count(1)
_open: Dict[int, dict] = {}      # sid -> begin event (all threads)
_sinks: List[Callable] = []

_jax = None                      # lazy: None = untried, False = absent


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def set_ring_size(n: int) -> None:
    """Re-bound the ring (keeps the newest events)."""
    global _ring
    n = max(int(n), 1)
    with _lock:
        _ring = deque(_ring, maxlen=n)


def ring() -> list:
    """Set-up events (pinned) first, then the newest per-call events."""
    with _lock:
        return list(_pinned) + list(_ring)


def clear() -> None:
    with _lock:
        _ring.clear()
        _pinned.clear()
        _open.clear()


def open_spans() -> list:
    """Begin events of every span currently open in ANY thread — the
    flight recorder dumps this to name what a hung/dying trainer was
    doing."""
    with _lock:
        return [dict(ev) for ev in _open.values()]


def add_sink(fn: Callable[[dict], None]) -> None:
    with _lock:
        if fn not in _sinks:
            _sinks.append(fn)


def remove_sink(fn: Callable) -> None:
    with _lock:
        if fn in _sinks:
            _sinks.remove(fn)


def _emit(ev: dict) -> None:
    with _lock:
        (_pinned if ev.get("setup") else _ring).append(ev)
        sinks = list(_sinks)
    for s in sinks:
        try:
            s(ev)
        except Exception:
            pass        # a broken sink must not break the traced code


def _trace_annotation(name: str):
    """jax.profiler.TraceAnnotation when jax is importable (so armed
    spans land in an active XProf trace); None otherwise. The import is
    resolved once and cached."""
    global _jax
    if _jax is None:
        try:
            import jax as _j
            _jax = _j
        except Exception:
            _jax = False
    if _jax is False:
        return None
    try:
        return _jax.profiler.TraceAnnotation(name)
    except Exception:
        return None


class span:
    """Context manager: `with span("ckpt.save", path=p): ...` records a
    begin/end pair (wall epoch + monotonic duration) into the ring and an
    XProf TraceAnnotation. Disarmed: one bool check."""

    __slots__ = ("name", "attrs", "_sid", "_p0", "_ann")
    _always = False              # setup_span: recorded armed or not

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        if not (_enabled or self._always):
            self._sid = None
            return self
        self._sid = next(_seq)
        self._p0 = time.perf_counter()
        ev = {"ev": "span_begin", "sid": self._sid, "name": self.name,
              "ts": time.time(), "thread": threading.get_ident(),
              "thread_name": threading.current_thread().name}
        if self.attrs:
            ev["attrs"] = {k: str(v) for k, v in self.attrs.items()}
        if self._always:
            _mark_setup(ev).append(self)
        with _lock:
            _open[self._sid] = ev
        _emit(ev)
        self._ann = _trace_annotation(self.name)
        if self._ann is not None:
            try:
                self._ann.__enter__()
            except Exception:
                self._ann = None
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._sid is None:
            return False
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        ev = {"ev": "span_end", "sid": self._sid, "name": self.name,
              "ts": time.time(),
              "dur_s": time.perf_counter() - self._p0}
        if exc_type is not None:
            ev["error"] = exc_type.__name__
        if self._always:
            stack = getattr(_tl, "setup", ())
            if self in stack:
                stack.remove(self)
            _mark_setup(ev)
        with _lock:
            _open.pop(self._sid, None)
        _emit(ev)
        return False


def _mark_setup(ev: dict) -> list:
    """Mark `ev` as a set-up event, with the set-up spans open on this
    thread (outermost first) as `within`; returns that stack."""
    stack = getattr(_tl, "setup", None)
    if stack is None:
        stack = _tl.setup = []
    ev["setup"] = True
    if stack:
        ev["within"] = "/".join(sp.name for sp in stack)
    return stack


class setup_span(span):
    """A span around work that runs once per trace or compile (never per
    step): recorded whether or not the registry is armed, kept in the
    ring's pinned part, and on this thread's stack of open set-up spans
    while open, so that compile events can say which phase they fired in."""

    __slots__ = ()
    _always = True


def setup_event(name: str, dur_s=None, **attrs) -> None:
    """One pinned set-up record without a block of its own (a duration
    jax.monitoring reports, a trace count): `within` names the set-up
    spans open on this thread when it fired, outermost first."""
    ev = {"ev": "setup_event", "name": name, "ts": time.time()}
    if dur_s is not None:
        ev["dur_s"] = float(dur_s)
    if attrs:
        ev["attrs"] = {k: str(v) for k, v in attrs.items()}
    _mark_setup(ev)
    _emit(ev)
