#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data: `BENCHMARK.json` names the cell, its
configuration and traffic mix and the metrics; `chipbench/cells/<cell>.json`,
the configuration's file, `chipbench/traffic/<mix>.json` say what to run;
the mix's `kind` names the driver (`chipbench/drivers/<kind>.py`); each
per-layer metric is `chipbench/layer_metrics/<name>.py`. This file names
none of them.

The last line of standard output is the result. Without the cell's chips
the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_cell(root, workload):
    """(manifest, entry, cell, config, traffic) of one cell, by name."""
    manifest = _load(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"chipbench: no cell {workload!r} in BENCHMARK.json "
                         f"({[w['name'] for w in manifest['workloads']]})")
    bench = os.path.join(root, manifest["paths"][0])
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    cell = _load(os.path.join(bench, "cells", workload + ".json"))
    config = _load(os.path.join(root, cfg_entry["file"]))
    traffic = _load(os.path.join(bench, "traffic",
                                 entry["traffic"] + ".json"))
    steps = cell.get("check_steps", traffic.get("check_steps"))
    for name, lim in cell.get("correct", {}).get("limits", {}).items():
        if isinstance(lim["limit"], list) and len(lim["limit"]) != steps:
            raise SystemExit(
                f"chipbench: cell {workload!r}: the limit of {name} is a "
                f"list of {len(lim['limit'])}, one number a checked step "
                f"is {steps}")
    return manifest, entry, cell, config, traffic


def metrics_of(manifest, group, workload, reported=None):
    """Names of the manifest's `group` metrics that this cell reports."""
    out = []
    for m in manifest[group]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        if (group == "per_layer" and "workloads" not in m
                and reported is not None and m["moves"] not in reported):
            continue
        out.append(m)
    return out


def layer_metric(name):
    """The reader of one per-layer metric: layer_metrics/<name>.py."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_devices(chips, require_chip=True):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_chip and d0.platform != "tpu":
        print(f"chipbench: needs a TPU; JAX reports platform "
              f"{d0.platform!r} ({len(devs)} device(s)). Nothing was run.",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"chipbench: the cell needs {chips} chip(s), JAX reports "
              f"{len(devs)}. Nothing was run.", file=sys.stderr)
        return None
    return devs


def make_ctx(root, workload, seed, seconds, trace=False, require_chip=True,
             t_start=None):
    """(manifest, driver module, run context) of one cell, or None
    without its chips. Shared by run.py, sweep.py and control.py."""
    manifest, entry, cell, config, traffic = load_cell(root, workload)
    chips = int(entry["chips"])
    devs = find_devices(chips, require_chip)
    if devs is None:
        return None
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)} used={chips}", flush=True)
    peaks = _load(os.path.join(HERE, "peaks.json")).get(d0.device_kind)
    if peaks is None and require_chip:
        raise SystemExit(f"chipbench: device_kind {d0.device_kind!r} is "
                         f"not in peaks.json; add it with its source")
    if require_chip:
        from paddle_tpu.framework.compile_cache import use_compile_cache
        print(f"compile cache: {use_compile_cache()}", flush=True)
    from chipbench import harness
    ctx = harness.Ctx(
        root=root, workload=workload, seed=int(seed), seconds=float(seconds),
        trace=bool(trace), chips=chips, config=config, traffic=traffic,
        cell=cell, peaks=peaks, devices=devs, on_chip=require_chip,
        t_start=T_START if t_start is None else t_start)
    driver = importlib.import_module("chipbench.drivers." + traffic["kind"])
    return manifest, driver, ctx


def run_cell(root, workload, seed, seconds, trace, require_chip=True,
             t_start=None):
    """Run one cell; returns the result object (None without its chips)."""
    made = make_ctx(root, workload, seed, seconds, trace, require_chip,
                    t_start)
    if made is None:
        return None
    manifest, driver, ctx = made
    from chipbench import trace_reduce
    d0, chips = ctx.devices[0], ctx.chips
    res = driver.run(ctx)

    for row in res["compared"]:
        print(f"compared: {row['name']} = {row['value']!r} limit "
              f"{row['limit']!r} {'ok' if row['ok'] else 'NOT OK'} "
              f"{row['note']}", flush=True)
    correct = all(row["ok"] for row in res["compared"])

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": chips, "memory_peak_bytes": res["peak_bytes"]}
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in metrics_of(manifest, "end_to_end", workload):
            if m["name"] in res["end_to_end"]:
                out["metrics"][m["name"]] = {
                    "value": res["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        run = res["run"]
        red = None
        if run.get("trace"):
            run["trace"]["events"] = trace_reduce.load(run["trace"]["dir"])
            red = trace_reduce.reduce(run["trace"]["events"])
            run["trace"]["reduced"] = red
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            out["breakdown"] = trace_reduce.breakdown(red)
            print("trace: layout " + json.dumps(
                run["trace"]["events"]["layout"])[:2000], flush=True)
            print("trace: idle by span " + json.dumps(
                red["idle_by_span_s"]), flush=True)
        for m in metrics_of(manifest, "per_layer", workload,
                            reported=res["end_to_end"]):
            value = layer_metric(m["name"]).compute(run)
            if value is None:
                continue          # the reader found nothing to read
            if isinstance(value, tuple):
                value, note = value
                print(f"layer metric: {m['name']} {note}", flush=True)
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    # last in the line: each number compared beside its limit, so that a
    # record which keeps only a refused run's last line keeps these
    out["compared"] = {
        row["name"]: {"value": row["value"] if math.isfinite(row["value"])
                      else repr(row["value"]),      # JSON has no nan or inf
                      "limit": row["limit"]}
        for row in res["compared"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    if out is None:
        return 1
    for name, row in out["compared"].items():       # standard error's end
        print(f"compared: {name} = {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
