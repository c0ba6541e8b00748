"""Device-side double-buffered batch prefetch (ref: the reference's
`use_buffer_reader` buffered_reader + pinned-memory data_feed pipeline,
fluid/operators/reader/buffered_reader.cc).

On TPU the host→device transfer is `jax.device_put` — an async dispatch,
so staging batch N+1 while the compiled step for batch N runs hides the
transfer entirely. `DevicePrefetcher` runs a staging thread that pulls
collated batches from its source (the worker pool's out-queue or the
synchronous producer), places every Tensor leaf on device — with the
active `ShardingPlan`'s `batch_spec` NamedSharding when a sharded
TrainStep is live, so multi-chip jobs stage straight into the mesh
layout — and hands the consumer up to `prefetch_factor` ready batches
through a bounded queue.

`dataloader.starved_seconds` is THE device-starvation signal: it sums the
time the training loop sat blocked on an empty staged-batch queue. If it
grows while `dataloader.producer_wait_seconds` stays flat, raise
`num_workers`; if the staged queue is always full and the counter still
grows, the step itself is the bottleneck.

Kill switch: FLAGS_dataloader_prefetch=false bypasses this module
entirely (DataLoader yields un-staged batches exactly as before).
"""
from __future__ import annotations

import queue
import threading
import time
import weakref
from typing import Any, Optional

from ..framework import core
from ..observability import goodput as _goodput
from ..observability import metrics as _m
from ..tensor import Tensor

__all__ = ["DevicePrefetcher", "set_active_plan", "active_plan"]

_STARVED = _m.counter(
    "dataloader.starved_seconds", "seconds the consumer (training loop) "
    "spent blocked on an empty staged-batch queue in STEADY STATE — the "
    "device-starvation signal (first-batch pipeline warmup is tracked "
    "separately in dataloader.warmup_seconds)")
_WARMUP = _m.counter(
    "dataloader.warmup_seconds", "seconds the consumer waited for the "
    "FIRST staged batch of each epoch (worker spin-up + first collate + "
    "first device transfer) — cold-start cost, not steady-state "
    "starvation")
_PREFETCH_DEPTH = _m.gauge(
    "dataloader.prefetch_depth", "device-staged batches ready when the "
    "consumer takes one")
_STAGE_FALLBACKS = _m.counter(
    "dataloader.stage_fallbacks", "batches that could not be staged into "
    "the active sharding plan's layout (stale plan / indivisible leading "
    "dim / multi-process mesh) and were placed unsharded instead — a "
    "growing count on a sharded job means every batch pays a device-side "
    "reshard inside the step")

# the sharding plan of the most recently constructed sharded TrainStep:
# loaders built independently of the step pick it up so batches stage
# straight into the mesh layout (jit then needs no host-side reshard).
# Held by WEAK reference — the plan's lifetime belongs to the TrainStep
# that owns it; once that step is discarded the registration lapses
# instead of pinning the plan (and its attached model) forever
_active_plan_ref = None
_plan_lock = threading.Lock()
_fallback_warned = False


def set_active_plan(plan) -> None:
    """Registered by jit.TrainStep when constructed with `shard=`; pass
    None to clear (tests / plan teardown)."""
    global _active_plan_ref
    with _plan_lock:
        _active_plan_ref = None if plan is None else weakref.ref(plan)


def active_plan():
    ref = _active_plan_ref
    return ref() if ref is not None else None


class _PrefetchEnd:
    __slots__ = ()


class _PrefetchRaise:
    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


def _map_structure(fn, obj):
    """Apply fn to Tensor leaves of a collated batch, everything else
    passes through. Containers go through the pytree registry so
    namedtuples keep their field constructor and dict subclasses their
    type (a hand-rolled type(obj)(generator) rebuild would crash a
    namedtuple batch on the default-enabled staging path)."""
    import jax

    return jax.tree_util.tree_map(
        lambda v: fn(v) if isinstance(v, Tensor) else v, obj,
        is_leaf=lambda v: isinstance(v, Tensor))


class DevicePrefetcher:
    """Iterate `source`, keeping up to `prefetch_factor` batches staged
    on device ahead of the consumer. `plan=None` consults the active
    TrainStep sharding plan at iteration time."""

    def __init__(self, source, prefetch_factor: int = 2, plan=None):
        self.source = source
        self.prefetch_factor = max(1, int(prefetch_factor))
        self.plan = plan

    def _stage(self, batch):
        import jax

        plan = self.plan if self.plan is not None else active_plan()

        if plan is not None:
            from jax.sharding import NamedSharding

            def place(t):
                try:
                    sh = NamedSharding(plan.mesh, plan.batch_spec(t.data))
                    return Tensor(jax.device_put(t.data, sh),
                                  stop_gradient=t.stop_gradient)
                except Exception as e:
                    # batch not placeable on the registered plan (stale
                    # plan from an earlier TrainStep, indivisible leading
                    # dim, multi-process mesh): stage unsharded rather
                    # than poison the epoch — but COUNT it and say so
                    # once, so a plan/mesh bug degrades loudly instead of
                    # silently resharding every batch inside the step
                    _STAGE_FALLBACKS.inc()
                    global _fallback_warned
                    if not _fallback_warned:
                        _fallback_warned = True
                        import warnings
                        warnings.warn(
                            "DevicePrefetcher: batch not placeable on the "
                            f"active sharding plan ({type(e).__name__}: "
                            f"{e}); staging unsharded (see "
                            "dataloader.stage_fallbacks)", stacklevel=2)
                    return Tensor(jax.device_put(t.data),
                                  stop_gradient=t.stop_gradient)
        else:
            # explicit device -> a COMMITTED array: the transfer is issued
            # now (async) instead of deferred to first use inside the step
            dev = jax.config.jax_default_device or jax.devices()[0]

            def place(t):
                return Tensor(jax.device_put(t.data, dev),
                              stop_gradient=t.stop_gradient)
        return _map_structure(place, batch)

    def __iter__(self):
        from . import _interruptible_put

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_factor)
        stop = threading.Event()
        src = iter(self.source)

        def put(item):
            _interruptible_put(q, item, stop)

        def run():
            try:
                for batch in src:
                    if stop.is_set():
                        break
                    put(self._stage(batch))
                    if stop.is_set():
                        break
            except BaseException as e:    # re-raised on the consumer side
                put(_PrefetchRaise(e))
                return
            put(_PrefetchEnd())

        t = threading.Thread(target=run, daemon=True,
                             name="paddle-io-prefetcher")
        t.start()
        try:
            first = True
            while True:
                t0 = time.perf_counter()
                item = q.get()
                waited = time.perf_counter() - t0
                if isinstance(item, _PrefetchEnd):
                    return      # end-of-epoch drain wait: not starvation
                if isinstance(item, _PrefetchRaise):
                    raise item.exc
                # the first wait of an epoch is pipeline COLD-START
                # (worker spin-up + first collate + first transfer), not
                # steady-state starvation — fold it into warmup_seconds
                # so starved_seconds stays a clean scale-up signal
                (_WARMUP if first else _STARVED).inc(waited)
                # feed the goodput ledger's data_wait bucket (skipped
                # when a timed_iter on this thread already times the
                # enclosing next() — the hapi fit path)
                _goodput.consumer_wait(waited)
                first = False
                _PREFETCH_DEPTH.set(q.qsize())
                yield item
        finally:
            stop.set()
            while True:                   # unblock a producer stuck in put
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            # closing the source (so a worker-pool source cancels its
            # epoch and shuts its pool down) must wait until the staging
            # thread has left it — close() on an executing generator
            # raises and the pool would leak. The staging thread always
            # exits once its pending batch lands (stop is set), so when
            # the 1s bounded join isn't enough, hand the close to a
            # reaper instead of blocking the consumer.
            if hasattr(src, "close"):
                def _close_src():
                    try:
                        src.close()
                    except Exception:
                        pass
                t.join(timeout=1.0)
                if t.is_alive():
                    # deliberately unowned: the whole point is to NOT
                    # block the consumer on the wedged staging thread
                    # graft-lint: disable=thread-hygiene
                    threading.Thread(
                        target=lambda: (t.join(), _close_src()),
                        daemon=True, name="paddle-io-prefetch-reaper",
                    ).start()
                else:
                    _close_src()
