"""Pass: metrics-registry namespace hygiene.

Every instrument-creating call site in `paddle_tpu/` —
`metrics.counter(...)`, `metrics.gauge(...)`, `metrics.histogram(...)`
(or through the conventional aliases `_m` / `_om` / `_metrics` /
`observability`) — must:

1. pass a LITERAL first argument (no f-strings, concatenation or
   variables: a computed id defeats grep, this lint, and dashboard
   queries alike),
2. use the `subsystem.name` snake_case shape the registry enforces at
   runtime (e.g. `ckpt.save_seconds`), and
3. be the ONLY creation site for that (kind, id) pair — one instrument,
   one home module; shared instruments are imported, not re-requested,
   so a typo'd near-duplicate cannot silently fork a metric into two
   series.

SPAN names ride the same namespace discipline (ISSUE 11): a
`span("...")` / `_span("...")` first argument that is a string literal
must be snake_case 'subsystem.name', and one span name has ONE home
module — the same literal from two different files forks a span family
the post-mortem tooling would have to re-merge (repeats within one
module are fine: a retry loop spans the same name at several sites).
Computed span names are allowed only as a literal-prefix concatenation
(`span("collective." + op)`): the prefix pins the subsystem while the
tail stays dynamic. Fully dynamic names (a bare variable/attribute) are
flagged — suppress with a rationale where the dynamism is the API
(profiler.RecordEvent forwarding user names).

TRACE EVENT names (ISSUE 18) are the third namespace riding this
discipline: every `tr.event("...")` / `req.trace.event("...")` call
site must pass a literal snake_case id that is REGISTERED in
`observability.reqtrace.EVENTS` — the runtime raises on unregistered
names, but only when the site executes; this lint catches the typo'd
event (which would fork a timeline series the trace tooling cannot
merge) before any request has to hit the path. A conditional between
two registered literals (`"resumed" if ... else "admitted"`) is fine —
both arms are validated. The vocabulary is read from reqtrace.py's AST,
not imported, so the linter never pays the jax import chain.

Collector-bridged ids (register_collector rows) are data, not creation
sites, and are out of scope here; the registry's own name validation
still covers them at runtime.
"""
from __future__ import annotations

import ast
import re

from ..core import REPO, FileContext, LintPass

KINDS = ("counter", "gauge", "histogram")
# module aliases the registry is conventionally imported under
ALIASES = {"metrics", "_m", "_om", "_metrics", "observability"}
NAME_RE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")
# the 'subsystem.' (or 'subsystem.partial_') left part of a
# concatenated span name
SPAN_PREFIX_RE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z0-9_]*$")
# callables that open a span; attribute form also matches
# `spans.span(...)` / `_spans.span(...)` / `obs.span(...)`
SPAN_FUNCS = {"span", "_span"}
SPAN_MODULES = {"spans", "_spans", "obs", "observability"}


def _creation_calls(tree):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in KINDS and \
                isinstance(fn.value, ast.Name) and fn.value.id in ALIASES:
            yield node, fn.attr


# receivers a request-trace conventionally binds to; `<x>.trace.event`
# also matches (the GenerationRequest.trace attribute form)
TRACE_RECEIVERS = {"tr", "trace"}
EVENT_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_REQTRACE_PATH = REPO / "paddle_tpu" / "observability" / "reqtrace.py"


def _load_trace_events():
    """The registered vocabulary, from reqtrace.py's AST: the module-level
    `EVENTS = frozenset((...))` literal. None when unreadable (the
    vocabulary checks then stand down; literal/shape checks still run)."""
    try:
        tree = ast.parse(_REQTRACE_PATH.read_text())
    except (OSError, SyntaxError):
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "EVENTS"
                for t in node.targets):
            val = node.value
            if isinstance(val, ast.Call) and val.args:
                val = val.args[0]
            try:
                return frozenset(ast.literal_eval(val))
            except ValueError:
                return None
    return None


def _trace_event_calls(tree):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr == "event"):
            continue
        recv = fn.value
        if (isinstance(recv, ast.Name) and recv.id in TRACE_RECEIVERS) \
                or (isinstance(recv, ast.Attribute)
                    and recv.attr == "trace"):
            yield node


def _event_name_literals(arg):
    """The literal candidates an event-name argument can resolve to:
    [name] for a string constant, both arms for a literal conditional,
    None when the argument is not statically known."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return [arg.value]
    if isinstance(arg, ast.IfExp) \
            and isinstance(arg.body, ast.Constant) \
            and isinstance(arg.body.value, str) \
            and isinstance(arg.orelse, ast.Constant) \
            and isinstance(arg.orelse.value, str):
        return [arg.body.value, arg.orelse.value]
    return None


def _span_calls(tree):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in SPAN_FUNCS:
            yield node
        elif isinstance(fn, ast.Attribute) and fn.attr == "span" and \
                isinstance(fn.value, ast.Name) and \
                fn.value.id in SPAN_MODULES:
            yield node


class MetricNamesPass(LintPass):
    name = "metric-names"
    description = ("metric ids must be literal, unique, snake_case "
                   "'subsystem.name'; span names literal (or literal-"
                   "prefixed) with one home module per name; trace "
                   "event names literal and registered in "
                   "reqtrace.EVENTS")
    severity = "error"
    scope = ("paddle_tpu/",)

    def begin(self, repo):
        self._seen = {}     # (kind, id) -> (relpath, line)
        self._span_seen = {}    # span name -> (relpath, line)
        self._events = _load_trace_events()

    def check_file(self, ctx: FileContext):
        out = []
        for node, kind in _creation_calls(ctx.tree):
            if not node.args:
                out.append(self.finding(
                    ctx, node.lineno,
                    f"metrics.{kind}(...) with no id argument"))
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant) and
                    isinstance(arg.value, str)):
                out.append(self.finding(
                    ctx, node.lineno,
                    f"metrics.{kind}(...) id must be a string LITERAL "
                    f"(computed ids defeat grep, this lint and "
                    f"dashboards)"))
                continue
            name = arg.value
            if not NAME_RE.match(name):
                out.append(self.finding(
                    ctx, node.lineno,
                    f"metric id {name!r} must be snake_case "
                    f"'subsystem.name' (e.g. 'ckpt.save_seconds')"))
                continue
            key = (kind, name)
            if key in self._seen:
                prev_path, prev_line = self._seen[key]
                out.append(self.finding(
                    ctx, node.lineno,
                    f"duplicate creation site for {kind} {name!r} "
                    f"(first at {prev_path}:{prev_line}) — import the "
                    f"existing instrument instead of re-requesting it"))
            else:
                self._seen[key] = (ctx.relpath, node.lineno)
        for node in _span_calls(ctx.tree):
            if not node.args:
                out.append(self.finding(
                    ctx, node.lineno, "span(...) with no name argument"))
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                name = arg.value
                if not NAME_RE.match(name):
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"span name {name!r} must be snake_case "
                        f"'subsystem.name' (e.g. 'ckpt.save')"))
                    continue
                prev = self._span_seen.get(name)
                if prev is not None and prev[0] != ctx.relpath:
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"span name {name!r} already used in "
                        f"{prev[0]}:{prev[1]} — one span name, one home "
                        f"module (rename, or hoist the shared site)"))
                else:
                    self._span_seen.setdefault(
                        name, (ctx.relpath, node.lineno))
            elif isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add) \
                    and isinstance(arg.left, ast.Constant) and \
                    isinstance(arg.left.value, str):
                if not SPAN_PREFIX_RE.match(arg.left.value):
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"span name prefix {arg.left.value!r} must pin "
                        f"the subsystem as \"subsystem.\" + dynamic_tail"))
            else:
                out.append(self.finding(
                    ctx, node.lineno,
                    "span name must be a string literal (or a "
                    "\"subsystem.\" + tail concatenation) — fully "
                    "dynamic names defeat grep and the post-mortem "
                    "tooling"))
        # reqtrace.py itself forwards a validated variable through
        # self.event(...) — its receiver is `self`, outside
        # TRACE_RECEIVERS, so the module needs no suppression.
        for node in _trace_event_calls(ctx.tree):
            if not node.args:
                out.append(self.finding(
                    ctx, node.lineno,
                    "trace .event(...) with no event-name argument"))
                continue
            names = _event_name_literals(node.args[0])
            if names is None:
                out.append(self.finding(
                    ctx, node.lineno,
                    "trace event name must be a string LITERAL (or a "
                    "conditional between two literals) — computed "
                    "names defeat grep and the timeline tooling"))
                continue
            for name in names:
                if not EVENT_NAME_RE.match(name):
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"trace event name {name!r} must be snake_case "
                        f"(e.g. 'prefill_chunk')"))
                elif self._events is not None and name not in self._events:
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"trace event {name!r} is not registered in "
                        f"observability.reqtrace.EVENTS — add it to the "
                        f"vocabulary (with a comment saying what it "
                        f"marks) or fix the typo"))
        return out
