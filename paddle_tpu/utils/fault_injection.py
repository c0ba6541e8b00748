"""Deterministic fault injection for robustness testing (chaos harness).

Named fault points are compiled into durability-critical paths
(checkpoint shard/metadata writes, the elastic train loop, rpc connects,
``paddle_tpu.save``) and do NOTHING unless a schedule is armed — the
disabled fast path is a single module-global bool check, so production
code pays no measurable overhead for carrying them.

Schedule grammar (``FLAGS_fault_inject`` env var, ``paddle.set_flags``,
or :func:`configure`): a comma/semicolon-separated list of

    <point>:<action>[:<arg>][@N]

where ``@N`` triggers on the N-th *hit* of that point (1-based,
default 1) in this process. Actions:

- ``raise[:ExcName]`` — raise :class:`FaultInjected` (or the named
  builtin exception: ``ConnectionError``, ``OSError``, ``TimeoutError``)
- ``crash[:code]`` — ``os._exit(code)`` (default 137), simulating
  SIGKILL/preemption with no cleanup, no atexit, no flush
- ``delay[:seconds]`` — sleep (default 1.0), simulating a hang/stall
- ``torn_write`` — truncate the file passed by the call site to half
  its bytes and CONTINUE, simulating a torn write that a crash made
  visible (the atomic-write helpers pass their tmp file, so the torn
  blob is then renamed into place exactly as a real torn commit would)

Examples::

    FLAGS_fault_inject=ckpt.write_shard:crash@2
    FLAGS_fault_inject=ckpt.write_meta:torn_write@1,elastic.train_step:delay:0.5@3
    FLAGS_fault_inject=rpc.connect:raise:ConnectionError@1

Hit/trigger counters are exposed through
``paddle_tpu.profiler.fault_injection_stats()`` for tests and chaos
telemetry. Known points (grep ``fault_point(`` for the live list):
``ckpt.write_shard``, ``ckpt.write_meta``, ``ckpt.write_index``,
``elastic.train_step``, ``elastic.restore``, ``rpc.connect``,
``io.save``, ``static.save_model``, ``static.save_params``,
``onnx.export``, and the coordinated-recovery plane (ISSUE 6):
``elastic.heartbeat`` (in the per-beat loop — ``crash`` kills the whole
worker mid-training like a preemption, ``raise`` kills only the beat
thread, simulating a zombie whose TTL expires), ``elastic.barrier``
(each recovery/health-barrier poll), ``elastic.connect`` (the
authenticated client connect), and ``launch.spawn`` (the supervisor's
per-incarnation worker spawn). The serving engine (ISSUE 10) adds
``serving.tick`` (top of every scheduler tick, inside the isolation
boundary — an armed ``raise`` exercises per-request quarantine, a
``delay`` a wedged tick the engine watchdog must catch),
``serving.admit`` (``add_request`` under the SLO layer), and
``serving.page_alloc`` (every KV page-pool allocation). The serving
fleet (ISSUE 17) adds ``router.dispatch`` (each replica dispatch
attempt — an armed ``raise`` exercises the bounded-retry failover
path), ``router.probe`` (each active /healthz probe — failures drive
ejection), and ``router.relaunch`` (each supervisor respawn of a dead
replica).

Every point literal is linted by graft-lint's ``fault-point-hygiene``
pass: unique to one module, ``subsystem.name`` snake_case, and listed
in the fault-point table of ``tools/FAULT_POINTS.md``.
"""
from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, List, Optional

__all__ = ["FaultInjected", "fault_point", "configure", "stats", "reset",
           "enabled"]


class FaultInjected(RuntimeError):
    """Raised by an armed ``raise`` fault (default exception type)."""


# exceptions a schedule may name; kept to types whose constructors take a
# plain message (arbitrary names would let a config string reach eval-ish
# behavior through the exception registry)
_EXC_TYPES = {
    "FaultInjected": FaultInjected,
    "RuntimeError": RuntimeError,
    "ConnectionError": ConnectionError,
    "OSError": OSError,
    "IOError": OSError,
    "TimeoutError": TimeoutError,
}

_CRASH_EXIT_CODE = 137          # parity with SIGKILL's 128+9

_lock = threading.Lock()
_enabled = False                 # fast-path guard: read without the lock
_plans: Dict[str, List[dict]] = {}   # point -> [{action, arg, at, fired}]
_hits: Dict[str, int] = {}           # point -> times reached while enabled
_triggered: Dict[str, int] = {}      # point -> times a fault actually fired


class FaultConfigError(ValueError):
    """Malformed FLAGS_fault_inject schedule."""


def _parse_entry(entry: str):
    head, sep, rest = entry.partition(":")
    point = head.strip()
    if not sep or not point or not rest.strip():
        raise FaultConfigError(
            f"fault_inject: expected '<point>:<action>[:<arg>][@N]', "
            f"got {entry!r}")
    rest = rest.strip()
    at = 1
    if "@" in rest:
        rest, _, n = rest.rpartition("@")
        try:
            at = int(n)
        except ValueError:
            raise FaultConfigError(
                f"fault_inject: bad '@N' in {entry!r}") from None
        if at < 1:
            raise FaultConfigError(
                f"fault_inject: @N must be >= 1 in {entry!r}")
    action, _, arg = rest.partition(":")
    action = action.strip()
    arg = arg.strip() or None
    if action not in ("raise", "crash", "delay", "torn_write"):
        raise FaultConfigError(
            f"fault_inject: unknown action {action!r} in {entry!r}")
    if action == "raise" and arg is not None and arg not in _EXC_TYPES:
        raise FaultConfigError(
            f"fault_inject: unknown exception {arg!r} in {entry!r} "
            f"(allowed: {sorted(_EXC_TYPES)})")
    if action == "delay" and arg is not None:
        try:
            float(arg)
        except ValueError:
            raise FaultConfigError(
                f"fault_inject: bad delay seconds in {entry!r}") from None
    if action == "crash" and arg is not None:
        try:
            int(arg)
        except ValueError:
            raise FaultConfigError(
                f"fault_inject: bad crash exit code in {entry!r}") from None
    if action == "torn_write" and arg is not None:
        raise FaultConfigError(
            f"fault_inject: torn_write takes no arg ({entry!r})")
    return point, {"action": action, "arg": arg, "at": at, "fired": False}


def configure(spec: Optional[str]) -> None:
    """(Re)arm the schedule; ``None``/empty disables and clears counters."""
    global _enabled
    plans: Dict[str, List[dict]] = {}
    for entry in (spec or "").replace(";", ",").split(","):
        entry = entry.strip()
        if not entry:
            continue
        point, plan = _parse_entry(entry)
        plans.setdefault(point, []).append(plan)
    with _lock:
        _plans.clear()
        _plans.update(plans)
        _hits.clear()
        _triggered.clear()
        _enabled = bool(plans)


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Zero counters and re-arm every plan (schedule kept)."""
    with _lock:
        _hits.clear()
        _triggered.clear()
        for plans in _plans.values():
            for p in plans:
                p["fired"] = False


def stats() -> dict:
    """{'enabled': bool, 'points': {name: {'hits': n, 'triggered': m}}}."""
    with _lock:
        names = set(_hits) | set(_triggered) | set(_plans)
        return {"enabled": _enabled,
                "points": {n: {"hits": _hits.get(n, 0),
                               "triggered": _triggered.get(n, 0)}
                           for n in sorted(names)}}


def _torn_write(path: str) -> None:
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(size // 2, 0) if size > 1 else 0)


def fault_point(name: str, file: Optional[str] = None) -> None:
    """Mark an injectable site. No-op (one bool check) unless armed."""
    if not _enabled:
        return
    with _lock:
        _hits[name] = hit = _hits.get(name, 0) + 1
        due = [p for p in _plans.get(name, ())
               if not p["fired"] and p["at"] == hit]
        for p in due:
            p["fired"] = True
        if due:
            _triggered[name] = _triggered.get(name, 0) + len(due)
    for p in due:
        action, arg = p["action"], p["arg"]
        if action == "delay":
            time.sleep(float(arg) if arg is not None else 1.0)
        elif action == "torn_write":
            if file is None:
                raise FaultInjected(
                    f"fault_inject: torn_write armed at {name!r} but the "
                    f"call site passed no file")
            _torn_write(file)
        elif action == "crash":
            sys.stderr.write(
                f"fault_inject: crash at {name!r} (hit {hit})\n")
            sys.stderr.flush()
            os._exit(int(arg) if arg is not None else _CRASH_EXIT_CODE)
        else:   # raise
            exc = _EXC_TYPES[arg] if arg is not None else FaultInjected
            raise exc(f"fault injected at {name!r} (hit {hit})")


def _fault_collector():
    """Registry bridge (observability.metrics.register_collector): the
    armed-path counters keep their own lock; snapshot/export polls them
    here so `prometheus_text()` carries chaos telemetry too."""
    st = stats()
    rows = [("gauge", "fault.armed", None, 1 if st["enabled"] else 0)]
    for n, v in st["points"].items():
        rows.append(("counter", "fault.hits_total",
                     {"point": n}, v["hits"]))
        rows.append(("counter", "fault.triggered_total",
                     {"point": n}, v["triggered"]))
    return rows


def _register_collector():
    try:
        from ..observability import metrics as _om
    except ImportError:
        # loaded standalone by file path (chaos tests import this module
        # without the package) — the harness stays stdlib-only there
        return
    _om.register_collector("fault_injection", _fault_collector)


_register_collector()


# arm from the environment at import — subprocess chaos tests set
# FLAGS_fault_inject before the interpreter starts; paddle.set_flags
# routes here for in-process control (framework/core._apply_flag)
configure(os.environ.get("FLAGS_fault_inject"))
