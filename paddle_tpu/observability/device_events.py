"""Per-execution device telemetry: a jax.monitoring duration-event
listener bridged into registry histograms, plus per-executable execution
accounting keyed by a stable tag stamped at trace time.

Closes the documented trace-time-only caveat on in-shard_map collective
accounting (distributed/collective.py): the host-side telemetry wrapper
there runs once per COMPILE for compiled collectives, so
`collective.calls_total` under-counts executed steps. The fix rides two
seams:

- `execution(tag)` — a context manager the owner of a compiled callable
  wraps around each invocation (jit.TrainStep stamps "train_step*"; the
  serving engine stamps "serving.decode"/"serving.ragged_step"/
  "serving.prefill"). Each exit observes `xla.dispatch_seconds{
  executable=tag}` — HOST-observed dispatch wall: exact on synchronous
  backends, a dispatch-side lower bound under async TPU dispatch. The
  series is NAMED for what it measures (ISSUE 18 honesty pass). Device
  time is not a series here: no runtime reports it to the host, and it
  is read from a profiler trace by the program's own scope names
  (observability/scopes.py; `chipbench/scope_reduce.py`).
- `note_traced_collective(op)` — called by the collective wrapper while
  a TRACE is in progress inside an open execution window. The noted ops
  become the tag's composition; every later execution of that tag then
  increments `collective.executed_calls_total{op=..., executable=tag}`
  by the composition counts — per-execution numbers derived from
  trace-time composition x execution count. A re-trace (new shapes)
  REPLACES the composition, so recompiles never double it.

The jax.monitoring listeners are registered at import. Armed or not,
they keep what happens once per trace or compile as SET-UP events in
`spans.ring()`: every backend compile and top-level jaxpr-to-MLIR
lowering (`xla.backend_compile`, `xla.to_mlir`, with jax's `fun_name`
and the set-up span open on the thread: a backend compile inside
`train_step.lower` is a small eager program, not the step), the
persistent cache's hits and misses (`xla.cache_hit`, `xla.cache_miss`),
and for a TrainStep the OUTERMOST jaxpr trace and its lowering
(`train_step.trace`, `train_step.to_mlir`: `jaxpr_trace_duration` also
fires for every inner jit, hundreds of times a step, so the one that
follows `note_step_traced()` is taken, never the sum). Armed, they also
feed `xla.compile_seconds{executable=tag}` and the goodput ledger's
`compile` bucket. These events fire on compiles and traces only: a
steady step never reaches the listener.
"""
from __future__ import annotations

import threading
import time
from typing import Dict

from . import goodput as _goodput
from . import metrics as _m
from . import spans as _spans

__all__ = ["execution", "tagged", "note_traced_collective",
           "note_step_traced", "install_listener", "current_tag",
           "tag_composition"]

# wide-range buckets: compiles run seconds-to-minutes, executes ms-to-s
_H_COMPILE = _m.histogram(
    "xla.compile_seconds",
    "XLA compile-phase durations (jax.monitoring events) by the "
    "executable tag active when they fired",
    buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 60.0, 300.0))
_H_DISPATCH = _m.histogram(
    "xla.dispatch_seconds",
    "HOST-observed wall seconds per dispatched call of a tagged "
    "executable; under async dispatch this is a dispatch-side LOWER "
    "BOUND on device time, not device execute seconds (those come "
    "from a profiler trace, by scope name)")
_C_COLL_EXEC = _m.counter(
    "collective.executed_calls_total",
    "per-EXECUTION collective counts: trace-time composition of a "
    "tagged executable x its execution count (closes the trace-time-"
    "only caveat on collective.calls_total for compiled collectives)")

_lock = threading.RLock()
# executable tag -> {op: count} recorded at its last trace
_tag_ops: Dict[str, Dict[str, int]] = {}

_tl = threading.local()          # .stack: [execution frames]

_listener_installed = False


class _Frame:
    __slots__ = ("tag", "t0", "fresh")

    def __init__(self, tag: str):
        self.tag = tag
        self.t0 = time.perf_counter()
        self.fresh: Dict[str, int] = {}


def current_tag():
    """The innermost open execution tag on this thread, or None."""
    stack = getattr(_tl, "stack", None)
    return stack[-1].tag if stack else None


def tag_composition(tag: str) -> Dict[str, int]:
    """The collective composition recorded at `tag`'s last trace."""
    with _lock:
        return dict(_tag_ops.get(tag, {}))


class execution:
    """`with execution("train_step"): compiled(...)` — times the call
    into xla.dispatch_seconds{executable=tag} and replays the tag's
    traced collective composition into per-execution counters.
    Disarmed: an object allocation + one bool check."""

    __slots__ = ("tag", "_frame")

    def __init__(self, tag: str):
        self.tag = tag
        self._frame = None

    def __enter__(self):
        if not _m.enabled():
            return self
        self._frame = _Frame(self.tag)
        stack = getattr(_tl, "stack", None)
        if stack is None:
            stack = _tl.stack = []
        stack.append(self._frame)
        return self

    def __exit__(self, exc_type, exc, tb):
        f = self._frame
        if f is None:
            return False
        stack = getattr(_tl, "stack", None)
        if stack and stack[-1] is f:
            stack.pop()
        self._frame = None
        _H_DISPATCH.observe(time.perf_counter() - f.t0, executable=f.tag)
        with _lock:
            if f.fresh:
                # this execution TRACED (first call or a re-trace):
                # the fresh note set IS the composition now — replace,
                # never append, so recompiles cannot double it
                _tag_ops[f.tag] = dict(f.fresh)
            comp = _tag_ops.get(f.tag)
        if comp and exc_type is None:
            for op, n in comp.items():
                _C_COLL_EXEC.inc(n, op=op, executable=f.tag)
        return False


class tagged:
    """Trace-only tag window: compile durations and traced-collective
    notes attribute to `tag`, but NO execution is counted (no
    xla.dispatch_seconds sample, no composition replay). Wraps explicit
    `.lower()` calls — which may populate the jit trace cache, so the
    composition they trace must be kept for later executions."""

    __slots__ = ("tag", "_frame")

    def __init__(self, tag: str):
        self.tag = tag
        self._frame = None

    def __enter__(self):
        if not _m.enabled():
            return self
        self._frame = _Frame(self.tag)
        stack = getattr(_tl, "stack", None)
        if stack is None:
            stack = _tl.stack = []
        stack.append(self._frame)
        return self

    def __exit__(self, exc_type, exc, tb):
        f = self._frame
        if f is None:
            return False
        stack = getattr(_tl, "stack", None)
        if stack and stack[-1] is f:
            stack.pop()
        self._frame = None
        if f.fresh:
            with _lock:
                _tag_ops[f.tag] = dict(f.fresh)
        return False


def note_traced_collective(op: str) -> None:
    """Record that a collective op was traced into the executable whose
    execution window is open on this thread. No-op outside a window or
    outside tracing."""
    if not _m.enabled():
        return
    stack = getattr(_tl, "stack", None)
    if not stack:
        return
    import jax
    if jax.core.trace_ctx.is_top_level():
        return                       # eager call, not a trace
    f = stack[-1]
    f.fresh[op] = f.fresh.get(op, 0) + 1


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_TO_MLIR_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "xla.cache_hit",
                 "/jax/compilation_cache/cache_misses": "xla.cache_miss"}


def note_step_traced(tag: str) -> None:
    """Called by a TrainStep as its traced body returns: the next
    jaxpr-trace duration on this thread is the step's own (the
    outermost), and the lowering that follows it is the step's."""
    _tl.step_traced = tag
    _tl.step_lowering = None


def _on_duration(event, duration, **kw) -> None:
    # exact compile-phase events only: a bare "compile" substring would
    # also match /jax/compilation_cache/compile_time_saved_sec — time
    # that was NOT spent (warm persistent cache), which would inject a
    # phantom compile stall bigger than the window wall
    if not event.startswith("/jax/core/compile/"):
        return
    duration = float(duration)
    if event == _TRACE_EVENT:
        tag = getattr(_tl, "step_traced", None)
        if tag is not None:
            _tl.step_traced, _tl.step_lowering = None, tag
            _spans.setup_event("train_step.trace", duration, executable=tag)
    elif event == _TO_MLIR_EVENT:
        tag = getattr(_tl, "step_lowering", None)
        if tag is not None:
            _tl.step_lowering = None
            _spans.setup_event("train_step.to_mlir", duration,
                               executable=tag)
        else:
            _spans.setup_event("xla.to_mlir", duration,
                               fun_name=kw.get("fun_name", ""))
    elif event == _BACKEND_COMPILE_EVENT:
        _spans.setup_event("xla.backend_compile", duration,
                           fun_name=kw.get("fun_name", ""))
    if not _m.enabled():
        return
    tag = current_tag() or "untagged"
    _H_COMPILE.observe(duration, executable=tag)
    _goodput.attribute("compile", duration)


def _on_event(event, **kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _spans.setup_event(name)


def install_listener() -> None:
    """Register the jax.monitoring listeners once per process (jax has
    no unregister; disarmed they only keep the set-up events)."""
    global _listener_installed
    if _listener_installed:
        return
    _listener_installed = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


install_listener()
