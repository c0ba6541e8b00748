"""From a profiler trace to device time by the PROGRAM's own names.

The program names what it compiles (`paddle_tpu/observability/scopes.py`):
every device operation's `op_name` holds the phase of the step
(`forward`, `backward`, `grad_sync`, `optimizer`), the layer path and a
component (`attn/qkv`, `mlp`, `head`, ...); backward operations carry
jax's `transpose(...)`, recomputed ones `rematted_computation`. This file
reads those names from the xplane itself (`load`), resolves each
operation through the ordered table `components.json` (`resolve`) and
adds up SELF time per component, direction and collective class
(`reduce`): the same self-time rule as `trace_reduce.self_times` (a
`while` holds its body). A fusion that crosses scopes counts whole under
the op_name XLA gave the fusion.

It also reads what the program records once per trace or compile
(`setup_phases`: the set-up events in `observability.spans.ring()`), and
names each idle gap of the device by the innermost host span, the
program's three `train_step.*` spans included.

A program that names nothing (the parent of the PR that added the
names) gives a reduction in which only kernels resolve; a run without a
trace gives None. Nothing here raises for lack of names.
"""
from __future__ import annotations

import json
import os
import re

from chipbench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PROGRAM_SPANS = ("train_step.call_args", "train_step.dispatch",
                 "train_step.write_back")
SPAN_NAMES = trace_reduce.SPAN_NAMES + PROGRAM_SPANS
_OP_NAME_STATS = ("tf_op", "op_name", "hlo_op_name", "long_name", "name")


def rules(path=None):
    with open(path or os.path.join(HERE, "components.json")) as f:
        return json.load(f)


# -- names ------------------------------------------------------------------

def tokens(op_name):
    """`jit(pure)/backward/transpose(jvp(head))/dot_general` ->
    [jit, pure, backward, transpose, jvp, head, dot_general]."""
    return [t for t in re.split(r"[/()]+", op_name or "") if t]


def _holds(toks, scope):
    want = scope.split("/")
    n = len(want)
    return any(toks[i:i + n] == want for i in range(len(toks) - n + 1))


def resolve(instr, op_name, table, parent_phase=None):
    """(component or None, direction, phase) of one device operation.
    direction: forward | backward | recomputed | update (the optimizer's
    and grad_sync's own work)."""
    toks = tokens(op_name)
    phase = next((t for t in toks if t in table["phases"]), None)
    component = work = None
    for row in table["components"]:
        if "scope" in row and toks and _holds(toks, row["scope"]):
            component = row["component"]
            break
        if "kernel" in row and not toks and re.search(row["kernel"], instr):
            component, work = row["component"], row.get("work")
            break
    if phase is None:
        phase = parent_phase
    if toks:
        if any(m in toks for m in table["recomputed_marks"]):
            direction = "recomputed"
        elif any(m in toks for m in table["backward_marks"]):
            direction = "backward"
        elif phase in ("optimizer", "grad_sync"):
            direction = "update"
        else:
            direction = "forward"
    else:
        # a Mosaic call without op_name: its kernel says which work it
        # does, the enclosing `while` in which pass it runs. Forward work
        # inside the backward pass is recomputation.
        direction = work or ("backward" if phase == "backward" else "forward")
        if work == "forward" and phase == "backward":
            direction = "recomputed"
    return component, direction, phase


def collective_class(instr, op_name, table, phase):
    """The class of a collective (or relayout copy), or None for an
    operation that is neither."""
    toks = tokens(op_name)
    is_coll = bool(re.search(table["collective_opcode"], instr))
    for row in table["collectives"]:
        if not re.search(row["opcode"], instr):
            continue
        if "scope" in row and not _holds(toks, row["scope"]):
            continue
        if "phase" in row and phase != row["phase"]:
            continue
        return row["class"]
    return "other" if is_coll else None


# -- the xplane -------------------------------------------------------------

def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, wire type, value) of one serialized protobuf
    message: varints as int, length-delimited fields as bytes. The
    xplane's schema (tsl/profiler/protobuf/xplane.proto) is small and
    stable; reading it by hand keeps tensorflow out of the run."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield field, wire, val


def _planes(xplane_bytes):
    """(plane name, {stat id: stat name}, [XEventMetadata bytes]) of
    every plane of a serialized XSpace."""
    for f, _, plane in _fields(xplane_bytes):
        if f != 1:                               # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for f2, _, v in _fields(plane):
            if f2 == 2:
                name = v.decode("utf-8", "replace")
            elif f2 == 5:                        # stat_metadata map entry
                for f3, _, v3 in _fields(v):
                    if f3 == 2:
                        fields = {f4: v4 for f4, _, v4 in _fields(v3)}
                        stat_names[fields.get(1, 0)] = fields.get(
                            2, b"").decode("utf-8", "replace")
            elif f2 == 4:                        # event_metadata map entry
                metas.extend(m for f3, _, m in _fields(v) if f3 == 2)
        yield name, stat_names, metas


def metadata_strings(xplane_bytes, plane_prefix="/device:TPU"):
    """{event name: {stat name: string}} from the EVENT METADATA of the
    matching planes: where the profiler keeps what is the same for every
    occurrence of an operation, its op_name (`tf_op`) among it. jax's
    ProfileData shows an event's own stats only."""
    out = {}
    for name, stat_names, metas in _planes(xplane_bytes):
        if not name.startswith(plane_prefix):
            continue
        for meta in metas:
            ev_name, strings = "", {}
            for f4, _, v4 in _fields(meta):
                if f4 == 2:
                    ev_name = v4.decode("utf-8", "replace")
                elif f4 == 5:                    # XStat
                    key = val = None
                    for f5, w5, v5 in _fields(v4):
                        if f5 == 1:
                            key = stat_names.get(v5, str(v5))
                        elif f5 == 5 and w5 == 2:
                            val = v5.decode("utf-8", "replace")
                        elif f5 == 7:            # ref to a stat name
                            val = stat_names.get(v5)
                    if key is not None and val is not None:
                        strings[key] = val
            if ev_name and strings:
                out.setdefault(ev_name, {}).update(strings)
    return out


def hlo_op_names(xplane_bytes):
    """{instruction name: op_name} from the HLO modules the profiler
    keeps in the `/host:metadata` plane (one serialized HloProto per
    module that ran, as an event metadata's bytes stat): the fallback
    for a profiler that keeps no op_name with its events. Where two
    modules share an instruction name the larger module's wins: the
    traced window runs the step."""
    modules = []
    for name, _, metas in _planes(xplane_bytes):
        if name != "/host:metadata":
            continue
        for meta in metas:
            for f4, _, stat in _fields(meta):
                if f4 != 5:
                    continue
                for f5, w5, proto in _fields(stat):
                    if f5 == 6 and w5 == 2:      # bytes_value: an HloProto
                        modules.append(_hlo_proto_names(proto))
    out = {}
    for names in sorted(modules, key=len):
        out.update(names)
    return out


def _hlo_proto_names(proto):
    names = {}
    for f, w, module in _fields(proto):
        if f != 1 or w != 2:                     # HloProto.hlo_module
            continue
        for f2, w2, comp in _fields(module):
            if f2 != 3 or w2 != 2:               # .computations
                continue
            for f3, w3, instr in _fields(comp):
                if f3 != 2 or w3 != 2:           # .instructions
                    continue
                iname = op = None
                for f4, w4, v in _fields(instr):
                    if f4 == 1 and w4 == 2:
                        iname = v.decode("utf-8", "replace")
                    elif f4 == 7 and w4 == 2:    # OpMetadata.op_name
                        op = next((v5.decode("utf-8", "replace")
                                   for f5, w5, v5 in _fields(v)
                                   if f5 == 2 and w5 == 2), None)
                if iname and op:
                    names[iname] = op
    return names


def _op_name_in(event_name, strings):
    m = re.search(r'op_name="([^"]*)"', event_name)
    if m:
        return m.group(1)
    found = None
    for key, value in (strings or {}).items():
        if "/" not in value:
            continue
        if key in _OP_NAME_STATS:
            return value
        if found is None and value.startswith(("jit(", "pjit(")):
            found = value
    return found


def load(trace_dir, span_names=SPAN_NAMES):
    """{"device": {plane: [[instruction, start_ns, dur_ns, op_name], ...]},
    "spans": [[name, start_ns, dur_ns], ...]} as plain lists."""
    import glob

    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    strings = metadata_strings(raw)
    by_instr = None                # the HLO modules, read only if needed
    data = ProfileData.from_serialized_xspace(raw)
    device, spans = {}, []

    def op_name_of(event_name):
        nonlocal by_instr
        op = _op_name_in(event_name, strings.get(event_name))
        if op is None:
            if by_instr is None:
                by_instr = hlo_op_names(raw)
            op = by_instr.get(trace_reduce.op_name(event_name))
        return op

    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    device[plane.name] = [
                        [trace_reduce.op_name(e.name), int(e.start_ns),
                         int(e.duration_ns), op_name_of(e.name)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    spans.sort(key=lambda s: s[1])
    return {"device": device, "spans": spans}


# -- the reduction ----------------------------------------------------------

def _self_with_parent(events):
    """[(instruction, op_name, self_ns, parent's op_name)] of nested
    events on one line: trace_reduce.self_times' rule, kept per event."""
    out, stack = [], []              # stack of [instr, op, end, self_ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            instr, op, _, self_ns = stack.pop()
            out.append((instr, op, self_ns, stack[-1][1] if stack else None))
    for instr, s, d, op in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][3] -= min(d, stack[-1][2] - s)
        stack.append([instr, op, s + d, d])
    close(float("inf"))
    return out


def reduce(trace, table=None, top=8):
    """Self seconds per device (averaged over the devices) in the traced
    window: by (component, direction), by collective class, unnamed by
    instruction; the busy time they add up to; the idle gaps by innermost
    span; the program's host spans."""
    table = table or rules()
    outer = [s for s in trace["spans"] if s[0] in trace_reduce.SPAN_NAMES]
    if not outer or not trace["device"]:
        return None
    w0 = outer[0][1]
    w1 = max(s + d for _, s, d in outer)
    n_dev = len(trace["device"])
    by, coll, unnamed, busy, named = {}, {}, {}, 0.0, 0.0
    coll_by = {}
    has_names = False
    busy_iv = []
    for plane, events in sorted(trace["device"].items()):
        clipped = [(n, max(s, w0), min(s + d, w1) - max(s, w0), op)
                   for n, s, d, op in events if s < w1 and s + d > w0]
        busy_iv.append(trace_reduce._union(
            [(s, s + d) for _, s, d, _ in clipped]))
        for instr, op, self_ns, parent_op in _self_with_parent(clipped):
            sec = self_ns / 1e9 / n_dev
            busy += sec
            has_names = has_names or bool(op)
            parent_phase = next((t for t in tokens(parent_op)
                                 if t in table["phases"]), None)
            component, direction, phase = resolve(instr, op, table,
                                                  parent_phase)
            cls = collective_class(instr, op, table, phase)
            if cls is not None:
                coll[cls] = coll.get(cls, 0.0) + sec
                key = (cls, trace_reduce.base_name(instr),
                       "/".join(tokens(op)[-4:]))
                coll_by[key] = coll_by.get(key, 0.0) + sec
                if component is not None or cls != "other":
                    named += sec
                continue
            if component is None:
                k = trace_reduce.base_name(instr)
                unnamed[k] = unnamed.get(k, 0.0) + sec
                continue
            named += sec
            by[(component, direction)] = by.get((component, direction),
                                                0.0) + sec
    spans = trace["spans"]

    def covering(mid):
        inner = None
        for name, s, d in spans:
            if s <= mid < s + d and (inner is None or d < inner[1]):
                inner = (name, d)
        return inner[0] if inner else "between spans"

    gap_by = {}
    for busy_dev in busy_iv:
        edges = [w0] + [x for iv in busy_dev for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                k = covering((a + b) // 2)
                gap_by[k] = gap_by.get(k, 0.0) + (b - a) / 1e9 / n_dev
    host = {}
    for name, s, d in spans:
        if name in PROGRAM_SPANS and w0 <= s < w1:
            host.setdefault(name, []).append(d / 1e9)
    return {
        "window_s": (w1 - w0) / 1e9, "busy_s": busy, "named_s": named,
        "has_op_names": has_names, "n_devices": n_dev,
        "component_s": by, "collective_s": coll,
        "collective_top": sorted(coll_by.items(), key=lambda kv: -kv[1])[:top],
        "unnamed_s": sorted(unnamed.items(), key=lambda kv: -kv[1])[:top],
        "unnamed_total_s": sum(unnamed.values()),
        "idle_by_span_s": sorted(gap_by.items(), key=lambda kv: -kv[1]),
        "host_span_s": host,
    }


def of_run(run):
    """The reduction of a traced run (computed once and kept on the run),
    or None: no trace, or a driver kind these names do not describe."""
    tr = run.get("trace")
    if run.get("kind") != "train" or not tr:
        return None
    if "scope_reduced" not in tr:
        tr["scope_reduced"] = reduce(load(tr["dir"]))
    return tr["scope_reduced"]


def group_s(red, group, directions=None, table=None):
    """Seconds of one of components.json's groups, all directions or
    the given ones."""
    members = (table or rules())["groups"][group]
    return sum(v for (c, d), v in red["component_s"].items()
               if c in members and (directions is None or d in directions))


def ms_per_step(run, group):
    """(value, note) of a `<group>_ms_per_step` metric, or None."""
    red = of_run(run)
    if not red or not red["has_op_names"]:
        return None
    steps = run["steps_traced"]
    parts = {d: group_s(red, group, (d,)) * 1e3 / steps
             for d in ("forward", "backward", "recomputed", "update")}
    total = sum(parts.values())
    if total <= 0:
        return None                # nothing in the trace carries the names
    return total, ("per device per step: " + " ".join(
        f"{d}={v:.3f}" for d, v in parts.items() if v))


def exposed_share(run, cls):
    """(value, note) of a `<class>_exposed_share` metric, or None. A
    class counts with its `<class>_copy` (a relayout's local copies); the
    note's `collectives` sum leaves the copies out and is what
    `collective_exposed_share` reads from the same trace."""
    red = of_run(run)
    if not red or run["chips"] < 2 or not red["has_op_names"]:
        return None
    pct = {k: 100.0 * v / red["window_s"]
           for k, v in sorted(red["collective_s"].items())}
    value = sum(v for k, v in pct.items() if k in (cls, cls + "_copy"))
    colls = sum(v for k, v in pct.items() if not k.endswith("_copy"))
    top = "; ".join(f"{c}:{i}@{op}={s:.4f}s"
                    for (c, i, op), s in red["collective_top"])
    return value, (
        "of the window; " + " ".join(f"{k}={v:.2f}%" for k, v in pct.items())
        + f" | collectives (no copies) sum={colls:.2f}% of which other "
        f"collectives={pct.get('other', 0.0):.2f}% | top: {top}")


# -- what the program records once per trace or compile ---------------------

def setup_phases(ring=None):
    """Where the seconds of the step's FIRST `TrainStep.lower()` went,
    from the set-up events in `observability.spans.ring()`:
    {"lower": s, "call_args": s, "forward": s, "backward": s,
     "grad_sync": s, "optimizer": s (each less the eager compiles that
     fired inside it), "trace": s, "to_mlir": s, "inner_compile": s,
     "inner_compiles": n, "inner_to_mlir": s, "rest": s, "traces": n,
     "retraces": n} or None where the program records none."""
    if ring is None:
        try:
            from paddle_tpu.observability import spans
        except ImportError:
            return None
        ring = spans.ring()
    setup = [ev for ev in ring if ev.get("setup")]
    traced = [ev for ev in setup if ev["name"] == "train_step.traced"]
    if not traced:
        return None
    out = {"traces": len(traced), "retraces": sum(
        1 for ev in traced
        if ev.get("attrs", {}).get("after_first_execution") == "True")}
    begin = next((i for i, ev in enumerate(setup)
                  if ev["name"] == "train_step.lower"
                  and ev["ev"] == "span_begin"), None)
    if begin is None:
        return out
    sid = setup[begin]["sid"]
    end = next((i for i, ev in enumerate(setup)
                if ev.get("sid") == sid and ev["ev"] == "span_end"), None)
    if end is None:
        return out
    inside = setup[begin + 1:end]
    keys = ("call_args", "forward", "backward", "grad_sync", "optimizer")
    out.update({k: 0.0 for k in keys + ("trace", "to_mlir", "inner_compile",
                                        "inner_to_mlir")})
    out["lower"] = setup[end]["dur_s"]
    out["inner_compiles"] = 0
    for ev in inside:
        name, dur = ev["name"], ev.get("dur_s")
        if dur is None:
            continue
        short = name[len("train_step."):]
        if ev["ev"] == "span_end" and short in keys:
            out[short] += dur
        elif name in ("train_step.trace", "train_step.to_mlir"):
            out[short] += dur
        elif name in ("xla.backend_compile", "xla.to_mlir"):
            k = ("inner_compile" if name == "xla.backend_compile"
                 else "inner_to_mlir")
            out[k] += dur
            out["inner_compiles"] += name == "xla.backend_compile"
            # an eager program compiled while a phase traced: its
            # seconds are not that phase's Python tracing
            for part in ev.get("within", "").split("/"):
                if part[len("train_step."):] in keys:
                    out[part[len("train_step."):]] -= dur
    out["rest"] = out["lower"] - sum(out[k] for k in keys + (
        "to_mlir", "inner_compile", "inner_to_mlir"))
    return out


def setup_note(ph, run):
    return (" ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                     for k, v in ph.items())
            + f" | trace_lower_s={run.get('lower_s')}")


# -- the same table on a compiled program's text ----------------------------

def instructions(text):
    """(computation, instruction, opcode, op_name) of every instruction
    of `compiled.as_text()`."""
    comp = None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", line)
        if not m:
            continue
        name, rest = m.groups()
        if rest.startswith("("):                # a tuple shape
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            rest = rest[i + 1:].lstrip()
        else:
            rest = rest.split(" ", 1)[1] if " " in rest else ""
        m2 = re.match(r"([\w\-]+)\(", rest)
        if m2:
            on = re.search(r'op_name="([^"]*)"', line)
            yield comp, name, m2.group(1), on.group(1) if on else None


def text_coverage(text, table=None,
                  opcodes=("dot", "convolution", "fusion", "custom-call")):
    """Of the instructions a device executes (not those inside a fused
    computation) that are matmuls, fusions, custom calls or collectives:
    how many resolve to a component or a collective class, and a
    Counter of (component, direction). Returns (resolved, total, counts,
    unresolved instruction names)."""
    import collections
    table = table or rules()
    fused = set()
    for line in text.splitlines():
        if " fusion(" in line:
            fused.update(re.findall(r"calls=%?([\w.\-]+)", line))
    counts, missed, total = collections.Counter(), [], 0
    for comp, name, opcode, op in instructions(text):
        if comp in fused:
            continue
        is_coll = bool(re.search(table["collective_opcode"], opcode))
        if opcode not in opcodes and not is_coll:
            continue
        total += 1
        component, direction, phase = resolve(name, op, table)
        cls = collective_class(opcode, op, table, phase) if is_coll else None
        if component is None and cls in (None, "other"):
            missed.append((name, opcode, op))
            continue
        counts[(cls or component, direction)] += 1
    return total - len(missed), total, counts, missed
