"""Kernel block-size autotuning with a persisted cache
(ref: paddle/phi/kernels/autotune/cache.cc + auto_tune_base.h — the
reference keys tuned kernel configs by shape signature and caches them
process-wide; here the cache also persists across processes as JSON so
one sweep serves every later run on the same device kind).

Candidates are timed by running the op inside one jitted `lax.scan`
loop (amortizes launch overhead), synchronized with a host transfer
(`float(x)`). Sweeps run only when explicitly enabled
(PADDLE_AUTOTUNE=1) or when `sweep=True` is passed — never silently
during training; cached winners are consulted unconditionally.

Layered lookup:
  1. in-process memo
  2. sweep cache file, written by sweeps: PADDLE_AUTOTUNE_CACHE, default
     `<checkout>/.paddle_tpu_autotune.json` (git-ignored). Nothing
     outside the checkout is read: a fresh checkout tunes from 3 alone.
  3. shipped defaults (kernels/autotune_defaults.json) — winners
     measured on real hardware, committed to the repo
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
from typing import Any, Callable, Dict, Optional, Sequence

__all__ = ["lookup", "record", "autotune", "cache_key", "device_kind"]

_lock = threading.Lock()
_memo: Dict[str, Any] = {}
_user_cache: Optional[Dict[str, Any]] = None
_defaults: Optional[Dict[str, Any]] = None

_DEFAULTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "autotune_defaults.json")


def _user_cache_path() -> str:
    from ..framework.compile_cache import CHECKOUT
    return os.environ.get(
        "PADDLE_AUTOTUNE_CACHE",
        os.path.join(CHECKOUT, ".paddle_tpu_autotune.json"))


def device_kind() -> str:
    """Normalized device tag the cache is keyed under (the platform name
    off-TPU). A backend that fails to initialise raises."""
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        return d.platform
    return d.device_kind.lower().replace(" ", "")


def cache_key(kernel: str, **shape_attrs) -> str:
    """Stable key: kernel name + sorted shape/config attrs + device kind.
    Keep attrs coarse (powers of two already quantize naturally) so one
    sweep covers one (kernel, shape-class, device) point."""
    parts = [kernel, device_kind()]
    parts += [f"{k}={shape_attrs[k]}" for k in sorted(shape_attrs)]
    return ":".join(parts)


def _load(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def lookup(key: str):
    """Best-known config for `key`, or None. Never sweeps.
    FLAGS_use_autotune=False disables tuned configs entirely (heuristic
    defaults only — the reference's global autotune kill switch)."""
    try:
        from ..framework import core
        if not core.get_bool_flag("FLAGS_use_autotune", True):
            return None
    except Exception:
        pass
    global _user_cache, _defaults
    with _lock:
        if key in _memo:
            return _memo[key]
        if _user_cache is None:
            _user_cache = _load(_user_cache_path())
        if _defaults is None:
            _defaults = _load(_DEFAULTS_PATH)
        for store in (_user_cache, _defaults):
            if key in store:
                _memo[key] = store[key]["best"]
                return _memo[key]
    return None


def _update_file(path: str, mutate) -> Dict[str, Any]:
    """Cross-PROCESS-safe read-modify-write of the user cache (advisor
    r3: two parallel sweep processes sharing PADDLE_AUTOTUNE_CACHE must
    not drop each other's winners): an fcntl flock serializes
    reload -> mutate -> atomic replace; where flock is unavailable the
    reload-merge still shrinks the race to the write itself (instead of
    trusting a stale in-memory snapshot)."""
    lock_path = path + ".lock"
    lf = None
    try:
        lf = open(lock_path, "a+")
        import fcntl
        fcntl.flock(lf, fcntl.LOCK_EX)
    except (OSError, ImportError):
        pass
    try:
        disk = _load(path)
        out = mutate(disk)
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(out, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass
        return out
    finally:
        if lf is not None:
            lf.close()       # releases the flock


def record(key: str, best, timings_ms: Optional[Dict[str, float]] = None):
    """Persist a sweep winner to the user cache (merge-on-write under an
    OS-level lock, atomic rename)."""
    global _user_cache
    path = _user_cache_path()
    entry: Dict[str, Any] = {"best": best}
    if timings_ms:
        entry["timings_ms"] = {k: round(v, 4)
                               for k, v in timings_ms.items()}
    with _lock:
        def mutate(disk):
            disk[key] = entry
            return disk

        _user_cache = _update_file(path, mutate)
        _memo[key] = best


def forget(key: str):
    """Drop a cache entry (memo + user file) — sweep repair path."""
    global _user_cache
    path = _user_cache_path()
    with _lock:
        _memo.pop(key, None)

        def mutate(disk):
            disk.pop(key, None)
            return disk

        _user_cache = _update_file(path, mutate)


class _CandidateTimeout(Exception):
    """A candidate blew its wall budget (a compile that never returns)
    — skip it; never let one candidate stall the sweep."""


@contextlib.contextmanager
def _candidate_deadline():
    """SIGALRM-armed context for one candidate's compile+measure: a
    per-candidate wall budget turns a candidate that never comes back
    into a skipped one. Main-thread only — elsewhere it degrades to a
    no-op. Limitation: SIGALRM only interrupts Python-level waits; a
    block inside jaxlib's C++ client fires the handler only when the C
    call returns."""
    import signal

    if not hasattr(signal, "SIGALRM"):
        yield  # no-op where SIGALRM doesn't exist (Windows)
        return
    try:
        budget = int(os.environ.get(
            "PADDLE_AUTOTUNE_CANDIDATE_TIMEOUT", "300"))
    except ValueError:
        import sys
        print("autotune: malformed PADDLE_AUTOTUNE_CANDIDATE_TIMEOUT "
              f"{os.environ['PADDLE_AUTOTUNE_CANDIDATE_TIMEOUT']!r}; "
              "using 300", file=sys.stderr)
        budget = 300
    if (budget <= 0 or threading.current_thread()
            is not threading.main_thread()):
        yield
        return

    def on_alarm(signum, frame):
        raise _CandidateTimeout()

    import time as _time
    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    armed_at = _time.monotonic()
    prev_remaining = signal.alarm(0)
    if prev_remaining:
        # never postpone a sooner outer deadline: the candidate budget
        # is capped by what's left of it
        budget = min(budget, prev_remaining)
    signal.alarm(budget)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)
        if prev_remaining:
            # an outer alarm was armed: re-arm what's left of its budget
            # rather than silently disarming it
            elapsed = int(_time.monotonic() - armed_at)
            signal.alarm(max(prev_remaining - elapsed, 1))


def _time_candidate(fn: Callable[[], Any], iters: int) -> float:
    """Median-of-3 wall time (ms per iteration) of a jitted loop."""
    import time

    import jax
    fn()  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn()
        # a host transfer ends the timing: nothing is left in flight
        jax.tree_util.tree_map(
            lambda x: float(x.reshape(-1)[0]) if hasattr(x, "reshape") else x,
            out)
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    return times[1] * 1e3


def sweeps_enabled() -> bool:
    if os.environ.get("PADDLE_AUTOTUNE", "0") == "1":
        return True
    try:  # flag consumers (ref FLAGS_use_autotune / exhaustive search)
        from ..framework import core
        if not core.get_bool_flag("FLAGS_use_autotune", True):
            return False
        return core.get_bool_flag("FLAGS_cudnn_exhaustive_search")
    except Exception:
        return False


def autotune(key: str, candidates: Sequence[Any],
             make_fn: Callable[[Any], Optional[Callable[[], Any]]],
             default: Any, iters: int = 8, sweep: Optional[bool] = None):
    """Return the best config for `key`.

    make_fn(candidate) returns a zero-arg callable running the op with
    that config (typically a jitted lax.scan loop of `iters` steps), or
    None / raises to skip the candidate. Cached winners are returned
    without running anything UNLESS sweep=True is passed explicitly
    (tools re-tuning after a kernel change must be able to re-measure);
    sweep=None means "sweep only if PADDLE_AUTOTUNE=1 and nothing is
    cached". Sweeps run only on a real accelerator (interpret-mode
    timings are meaningless), and a sweep where every candidate failed
    records NOTHING — the default must not masquerade as a winner.
    """
    forced = sweep is True
    hit = lookup(key)
    if hit is not None and not forced:
        return hit
    if sweep is None:
        sweep = sweeps_enabled()
    if not sweep or device_kind() == "cpu":
        return hit if hit is not None else default
    timings: Dict[str, float] = {}
    best, best_t = default, float("inf")
    for cand in candidates:
        try:
            with _candidate_deadline():
                fn = make_fn(cand)
                if fn is None:
                    continue
                t = _time_candidate(fn, iters)
        except _CandidateTimeout:
            import sys
            print(f"autotune: candidate {cand} for {key} exceeded "
                  "PADDLE_AUTOTUNE_CANDIDATE_TIMEOUT — skipped",
                  file=sys.stderr)
            continue
        except Exception:
            continue  # candidate doesn't compile/fit — skip
        timings[str(cand)] = t
        if t < best_t:
            best, best_t = cand, t
    if timings:
        record(key, best, timings)
    return best
