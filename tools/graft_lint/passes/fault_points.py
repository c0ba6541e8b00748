"""Pass: fault-point namespace hygiene (same contract shape as
flags-hygiene, applied to the chaos harness).

Every fault-injection site in `paddle_tpu/` — a direct
`fault_point("name")` call, a `fault_name="name"` keyword forwarded
through a helper (`framework.io.atomic_write`,
`distributed._net.connect_with_retry`), or a `fault_name` parameter
DEFAULT — must:

1. name the point with a string LITERAL (a computed point defeats grep,
   this lint, and every `FLAGS_fault_inject` schedule anyone will ever
   write). The only non-literal form allowed is forwarding a parameter
   itself named `fault_name` — the helper idiom;
2. use the `subsystem.name` snake_case shape the schedule grammar
   assumes (e.g. `ckpt.write_shard`, `serving.tick`);
3. live in ONE module: the same point name appearing in two files means
   either a copy-paste or two unrelated sites sharing a schedule entry
   by accident — both make `<point>:<action>@N` hit counts ambiguous.
   (Multiple sites in one file are fine: `elastic.restore` fires from
   two branches of one logical operation.);
4. be listed in the fault-point table of
   `tools/FAULT_POINTS.md` (between the
   `fault-point-table:begin/end` markers) — an undocumented point is a
   chaos lever nobody can find, and a documented point with no live
   site (the inverse check, full-scope runs only) is a runbook lying
   about coverage.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Set, Tuple

from ..core import FileContext, Finding, LintPass

RUNBOOK_RELPATH = "tools/FAULT_POINTS.md"
NAME_RE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")
_TABLE_BEGIN = "<!-- fault-point-table:begin -->"
_TABLE_END = "<!-- fault-point-table:end -->"
_ROW_RE = re.compile(r"^\|\s*`([^`]+)`")


def parse_runbook_table(runbook: Path) -> Set[str]:
    """Point names from the marked markdown table (first backticked
    cell of each row)."""
    text = runbook.read_text()
    if _TABLE_BEGIN not in text or _TABLE_END not in text:
        raise RuntimeError(
            f"fault-point-hygiene: no {_TABLE_BEGIN} .. {_TABLE_END} "
            f"table found in {runbook} — the fault-injection runbook "
            f"table moved; update tools/graft_lint/passes/"
            f"fault_points.py or restore the markers")
    seg = text.split(_TABLE_BEGIN, 1)[1].split(_TABLE_END, 1)[0]
    points: Set[str] = set()
    for line in seg.splitlines():
        m = _ROW_RE.match(line.strip())
        if m:
            points.add(m.group(1))
    return points


def _point_names(node: ast.Call) -> Tuple[List[Tuple[str, int]],
                                          List[Tuple[int, str]]]:
    """(literal (name, line) pairs, (line, problem) pairs) for one
    call."""
    names: List[Tuple[str, int]] = []
    bad: List[Tuple[int, str]] = []
    fn = node.func
    is_fp = ((isinstance(fn, ast.Name) and fn.id == "fault_point")
             or (isinstance(fn, ast.Attribute)
                 and fn.attr == "fault_point"))
    if is_fp:
        if not node.args:
            bad.append((node.lineno, "fault_point(...) with no point "
                        "name argument"))
        else:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                            str):
                names.append((arg.value, node.lineno))
            elif not (isinstance(arg, ast.Name)
                      and arg.id == "fault_name"):
                bad.append((node.lineno,
                            "fault_point(...) name must be a string "
                            "LITERAL (or a forwarded parameter itself "
                            "named `fault_name`) — a computed point "
                            "defeats grep, this lint, and every "
                            "FLAGS_fault_inject schedule"))
    for kw in node.keywords:
        if kw.arg != "fault_name":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            names.append((v.value, node.lineno))
        elif not (isinstance(v, ast.Name) and v.id == "fault_name"):
            bad.append((node.lineno,
                        "fault_name= must be a string LITERAL (or a "
                        "forwarded `fault_name` parameter)"))
    return names, bad


def _default_names(node) -> List[Tuple[str, int]]:
    """`fault_name` parameter defaults in a function definition."""
    out: List[Tuple[str, int]] = []
    args = node.args
    pos = args.posonlyargs + args.args
    for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        if a.arg == "fault_name" and isinstance(d, ast.Constant) and \
                isinstance(d.value, str):
            out.append((d.value, node.lineno))
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if a.arg == "fault_name" and isinstance(d, ast.Constant) and \
                isinstance(d.value, str):
            out.append((d.value, node.lineno))
    return out


class FaultPointsPass(LintPass):
    name = "fault-point-hygiene"
    description = ("fault_point literals must be unique to one module, "
                   "snake_case 'subsystem.name', and listed in the "
                   "runbook fault-point table")
    severity = "error"
    scope = ("paddle_tpu/",)

    def begin(self, repo):
        self._repo = repo
        self._documented: Set[str] = parse_runbook_table(
            repo / RUNBOOK_RELPATH)
        self._owner: Dict[str, Tuple[str, int]] = {}
        self._used: Set[str] = set()

    def check_file(self, ctx: FileContext):
        out: List[Finding] = []
        names: List[Tuple[str, int]] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                got, bad = _point_names(node)
                names.extend(got)
                for line, msg in bad:
                    out.append(self.finding(ctx, line, msg))
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                names.extend(_default_names(node))
        for nm, line in names:
            self._used.add(nm)
            if not NAME_RE.match(nm):
                out.append(self.finding(
                    ctx, line,
                    f"fault point {nm!r} must be snake_case "
                    f"'subsystem.name' (e.g. 'serving.tick')"))
                continue
            owner = self._owner.setdefault(nm, (ctx.relpath, line))
            if owner[0] != ctx.relpath:
                out.append(self.finding(
                    ctx, line,
                    f"fault point {nm!r} already lives in "
                    f"{owner[0]}:{owner[1]} — one point, one module "
                    f"(a schedule's @N hit count is ambiguous across "
                    f"unrelated sites); pick a new subsystem.name"))
            if nm not in self._documented:
                out.append(self.finding(
                    ctx, line,
                    f"fault point {nm!r} is not listed in the "
                    f"fault-point table of {RUNBOOK_RELPATH} — add a "
                    f"row (between the fault-point-table markers) so "
                    f"the chaos lever is discoverable"))
        return out

    def finish(self):
        if not self.scanned_full_scope:
            return []
        out = []
        for nm in sorted(self._documented - self._used):
            out.append(Finding(
                RUNBOOK_RELPATH, 0, self.name,
                f"documented fault point {nm!r} has no live "
                f"fault_point site — drop the runbook row or restore "
                f"the site", severity="warning"))
        return out
