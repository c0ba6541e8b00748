"""From a profiler trace (xplane) to the numbers the layer metrics read.

`load(dir)` turns the newest `.xplane.pb` under `dir` into plain lists:
{"device": {plane: [(name, start_ns, dur_ns), ...]},   the "XLA Ops" line
 "spans": [(name, start_ns, dur_ns), ...]}             chipbench's own
host spans (`jax.profiler.TraceAnnotation`). `reduce(events, spans)`
works on those lists alone, so it is tested on a small recorded trace.

The window is what chipbench's spans cover: from the first span's start
to the last span's end. Device operations nest on the ops line (a
`while` holds its body's operations), so busy time is the UNION of their
intervals and time by name is SELF time: an operation's duration less
what its children cover.
"""
from __future__ import annotations

import glob
import os
import re

SPAN_NAMES = ("train_step", "engine.step", "add_request")
OPS_LINE = "XLA Ops"
# HLO collectives by their opcode-derived names and by the names jax's
# shard_map primitives give them (the step's TP all-reduces are `psum.N`)
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|^psum|^ppermute|^pmax|^pmin|^all_gather"
    r"|^all_to_all|^psum_scatter|^reduce_scatter")


def op_name(event_name):
    """The trace names a device operation by its whole HLO line
    (`%fusion.3 = bf16[...] fusion(...)`): keep the instruction's name."""
    m = re.match(r"%?([\w.\-]+) = ", event_name)
    return m.group(1) if m else event_name


def load(trace_dir, span_names=SPAN_NAMES):
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    device, spans, layout = {}, [], {}
    for plane in data.planes:
        layout[plane.name] = [ln.name for ln in plane.lines]
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return {"device": device, "spans": spans, "layout": layout}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events):
    """{name: self seconds} of nested events on one line."""
    total, stack = {}, []                 # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            total[name] = total.get(name, 0) + self_ns
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    close(float("inf"))
    return {k: v / 1e9 for k, v in total.items()}


def base_name(op):
    """`fusion.123` -> `fusion`; a Mosaic kernel keeps its whole name."""
    return re.sub(r"[.\d]+$", "", op) or op


def reduce(trace, top=10):
    """The traced window in numbers. Per device and averaged over them:
    busy_s (union), window_s, idle share, self seconds by operation name,
    collective self seconds (on the serial ops line a collective that is
    on the line keeps compute off it: its self time is its exposed
    part), and the longest idle gaps, each named by the chipbench span
    that covers its middle."""
    spans = trace["spans"]
    if not spans or not trace["device"]:
        return None
    w0 = spans[0][1]
    w1 = max(s + d for _, s, d in spans)
    window_s = (w1 - w0) / 1e9
    per_dev, by_name, coll, gaps = [], {}, [], []
    for plane, events in sorted(trace["device"].items()):
        clipped = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                   for n, s, d in events if s < w1 and s + d > w0]
        busy = _union([(s, s + d) for _, s, d in clipped])
        busy_s = sum(e - s for s, e in busy) / 1e9
        per_dev.append(busy_s)
        st = self_times(clipped)
        for n, v in st.items():
            by_name[n] = by_name.get(n, 0.0) + v
        coll.append(sum(v for n, v in st.items() if _COLLECTIVE.search(n)))
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) // 2))
    n_dev = len(per_dev)
    ops = {}
    for n, v in by_name.items():
        ops[base_name(n)] = ops.get(base_name(n), 0.0) + v / n_dev

    def covering(mid):
        inner = None
        for name, s, d in spans:
            if s <= mid < s + d and (inner is None or d < inner[1]):
                inner = (name, d)
        return inner[0] if inner else "between spans"

    gap_by = {}
    for dur, mid in gaps:
        k = covering(mid)
        gap_by[k] = gap_by.get(k, 0.0) + dur / 1e9 / n_dev
    longest = sorted(gaps, reverse=True)[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(per_dev) / n_dev,
        "busy_s_per_device": per_dev,
        "idle_share": 1.0 - (sum(per_dev) / n_dev) / window_s,
        "op_self_s": dict(sorted(by_name.items(), key=lambda kv: -kv[1])),
        "op_s": sorted(ops.items(), key=lambda kv: -kv[1]),
        "collective_exposed_s": sum(coll) / n_dev,
        "idle_by_span_s": sorted(gap_by.items(), key=lambda kv: -kv[1]),
        "longest_gaps": [(covering(mid), dur / 1e9)
                         for dur, mid in longest],
        "n_devices": n_dev,
    }


def breakdown(red, top=10):
    """The contract's `breakdown`: device operations that took most
    time, and idle time by what the host was doing."""
    return {"device_ops": [[n, s] for n, s in red["op_s"][:top]],
            "idle_gaps": [[n, s] for n, s in red["idle_by_span_s"][:top]]}
