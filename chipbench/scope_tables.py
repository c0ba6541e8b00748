"""Per-layer metrics that read a traced run through a components table
of their OWN (`components_<model_type>.json` beside `components.json`):
the same reduction (`scope_reduce.reduce`), other rows and groups. The
trace is loaded and reduced once a table and kept on the run.

A reader several architectures share asks `table_of` and `costs_of` for
the files of the run's own `model_type`, so a new configuration brings
`components_<model_type>.json` and `costs_<model_type>.py`, lists its
cell under the metric, and edits no reader.
"""
from __future__ import annotations

import importlib
import os

from chipbench import scope_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _kind(run):
    return (run.get("config") or {}).get("model_type")


def table_of(run, group, first):
    """The components table a reader of `group` reads this run through:
    `components_<model_type>.json` where that file is there and has the
    group, else `first`, the table of the architecture the reader was
    written for."""
    own = f"components_{_kind(run)}.json"
    path = os.path.join(HERE, own)
    if own != first and os.path.exists(path) and group in scope_reduce.rules(
            path)["groups"]:
        return own
    return first


def costs_of(run, function, first=None):
    """The module whose `function` counts this run's required work:
    `chipbench.costs_<model_type>` where it is there and has the
    function, else `chipbench.<first>`, or None where the reader names
    no `first`: an architecture that brings no count of it."""
    kind = _kind(run)
    if kind and os.path.exists(os.path.join(HERE, f"costs_{kind}.py")):
        own = importlib.import_module(f"chipbench.costs_{kind}")
        if hasattr(own, function):
            return own
    return first and importlib.import_module("chipbench." + first)


def reduced(run, table_file):
    """(reduction, table) of a traced run under `table_file`, or None: no
    trace, a driver kind these names do not describe, or a program that
    names nothing (the parent of the PR that added the names)."""
    base = scope_reduce.of_run(run)
    if not base or not base["has_op_names"]:
        return None
    tr = run["trace"]
    key = "scope_reduced:" + table_file
    if key not in tr:
        table = scope_reduce.rules(os.path.join(HERE, table_file))
        if "scope_loaded" not in tr:
            tr["scope_loaded"] = scope_reduce.load(tr["dir"])
        tr[key] = (scope_reduce.reduce(tr["scope_loaded"], table=table),
                   table)
    return tr[key]


def ms_per_step(run, table_file, group):
    """(value, note) of a `<group>_ms_per_step` metric under
    `table_file`, or None."""
    got = reduced(run, table_file)
    if got is None:
        return None
    red, table = got
    steps = run["steps_traced"]
    parts = {d: scope_reduce.group_s(red, group, (d,), table) * 1e3 / steps
             for d in ("forward", "backward", "recomputed", "update")}
    total = sum(parts.values())
    if total <= 0:
        return None
    by = {}
    for (c, _), v in red["component_s"].items():
        by[c] = by.get(c, 0.0) + v * 1e3 / steps
    grouped = {c for members in table["groups"].values() for c in members}
    rest = {c: v for c, v in by.items() if c not in grouped}
    rest["unnamed"] = red["unnamed_total_s"] * 1e3 / steps
    return total, ("per device per step: " + " ".join(
        f"{d}={v:.3f}" for d, v in parts.items() if v) + " | " + " ".join(
        f"{c}={by.get(c, 0.0):.3f}" for c in table["groups"][group])
        + " | in no group of this table: " + " ".join(
        f"{c}={v:.3f}" for c, v in sorted(rest.items())))


def roofline(run, table_file, group, flops, byts, what):
    """(share %, note) of a group's device time against the least time
    the chip could take for `flops` and `byts` (of the traced steps in
    all), or None."""
    from chipbench import costs
    got = reduced(run, table_file)
    if got is None or not run.get("peaks"):
        return None
    red, table = got
    spent = scope_reduce.group_s(red, group, None, table)
    if spent <= 0:
        return None
    least, bound = costs.roofline_s(flops, byts, run["peaks"])
    return 100.0 * least / spent, (
        f"bound={bound} least_s={least:.6f} device_s={spent:.6f} over "
        f"{run['steps_traced']} steps; {what}")
