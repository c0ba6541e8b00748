#!/usr/bin/env python3
"""Readings for setting `correct`'s limits, by hand and not in any run:

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 \
        [--controls all|none|11,12] [--faults none|all|11] [--seconds 12]

For each seed, in this one process, the cell's driver gives the numbers
of a sound run; on the seeds of `--controls` those of the control (the
reference in the lower precisions the cell's file lists under
`correct.controls`, put in the program's place); on the seeds of
`--faults` those of the reference under each gross fault the cell's file
lists under `correct.faults` (the trainer settings it changes). A loss
gap is read a checked step (`loss_gap.step<n>`) whatever form its limit
has. One JSON line per seed, the rows' notes (both sides' losses, the
worst leaves) under "notes"; the last line gathers, per number, the sound
runs' largest and each control's and fault's smallest.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None, root=ROOT, require_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--controls", default="all")
    ap.add_argument("--faults", default="none")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from chipbench import harness, run
    seeds = [int(s) for s in args.seeds.split(",")]

    def asked(which):
        """The seeds of `--controls` or `--faults`."""
        return set() if which == "none" else set(seeds) if which == "all" \
            else {int(s) for s in which.split(",")}

    with_controls, with_faults = asked(args.controls), asked(args.faults)
    summary = {}
    for seed in seeds:
        made = run.make_ctx(root, args.workload, seed, args.seconds,
                            require_chip=require_chip,
                            t_start=time.perf_counter())
        if made is None:
            return 1
        _, driver, ctx = made
        out = driver.control(ctx, controls=seed in with_controls,
                             faults=seed in with_faults)
        harness.release()
        notes = {k: {r["name"]: r["note"] for i, r in enumerate(rows)
                     if i == 0 or not r["name"].startswith("loss_gap")}
                 for k, rows in out.items()}   # the loss rows share a note
        print(json.dumps({"seed": seed, **{
            k: {r["name"]: r["value"] for r in rows}
            for k, rows in out.items()}, "notes": notes}), flush=True)
        for k, rows in out.items():
            for r in rows:
                s = summary.setdefault(r["name"], {})
                if k == "sound":
                    s["sound_largest"] = max(s.get("sound_largest", 0.0),
                                             r["value"])
                else:
                    key = k + "_smallest"
                    s[key] = min(s.get(key, float("inf")), r["value"])
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
