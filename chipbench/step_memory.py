"""The compiled step's two memory set-up events, as their readers take
them from `observability.spans.ring()` (attributes arrive as strings):

`train_step.memory`, once a `TrainStep.lower().compile()`: the compiler's
count of the executable, a device's share under SPMD (`argument_bytes`,
`output_bytes`, `alias_bytes`, `temp_bytes`, `generated_code_bytes`, their
sum `sum_bytes`; `peak_bytes`: the compiler's own peak, arguments and the
program's fullest moment, which is what its refusal "used X of Y" prints,
the sum on a backend that gives none; the device's `bytes_limit` where
there is one).
`train_step.residuals`, once a key ("scope:taped op") a trace and one total
under "*": what the forward keeps for the backward (`bytes`, `arrays`; the total also
`state_bytes`: the step's own inputs among what the pullbacks hold).
`train_step.kept`, once a kernel call whose stamped residuals the armed
remat policy keeps (`kept`, `bytes` of one call).

Every function returns None where the program records no such event (a
tree before PR 36): the metric is then left out of the line."""
from __future__ import annotations


def _ring(ring):
    if ring is not None:
        return ring
    from chipbench import scope_reduce
    if scope_reduce.setup_phases() is None:
        return []       # no trace of a step on record: none of these either
    from paddle_tpu.observability import spans
    return spans.ring()


def _numbers(ev):
    out = {k: int(v) if v.lstrip("-").isdigit() else v
           for k, v in ev.get("attrs", {}).items()}
    if "dur_s" in ev:
        out["dur_s"] = ev["dur_s"]
    return out


def _events(ring, name):
    return [_numbers(ev) for ev in _ring(ring)
            if ev.get("setup") and ev.get("name") == name]


def memory(ring=None):
    """The numbers of the first `train_step.memory` event, or None."""
    found = _events(ring, "train_step.memory")
    return found[0] if found else None


def residuals(ring=None):
    """(total, [(scope, bytes, arrays), ...] largest first) of the first
    trace that left a ledger, or None."""
    found = _events(ring, "train_step.residuals")
    total = next((ev for ev in found if ev["scope"] == "*"), None)
    if total is None:
        return None
    rows = [(ev["scope"], ev["bytes"], ev["arrays"]) for ev in found
            if ev["scope"] != "*" and ev["trace"] == total["trace"]
            and ev["executable"] == total["executable"]]
    return total, sorted(rows, key=lambda r: -r[1])


def kept(ring=None):
    """{name: [calls, bytes of one call]} over the `train_step.kept`
    events (a scan around a call stacks that many a turn)."""
    out = {}
    for ev in _events(ring, "train_step.kept"):
        cell = out.setdefault(ev["kept"], [0, ev["bytes"]])
        cell[0] += 1
    return out


def runtime_peak(chips):
    """The runtime's `peak_bytes_in_use` of the fullest device, for the
    note: the process's count at the time of reading, which the step's
    temporaries never showed in (PERF.md section 4)."""
    import jax

    from chipbench import harness
    return harness.peak_bytes(jax.local_devices()[:chips])
