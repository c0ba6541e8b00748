#!/usr/bin/env python3
"""Find a serving cell's knee once, by hand and not in any run:

    python3 chipbench/sweep.py --workload <cell> --rates 2,4,8,16 [--seconds 20]

One engine, each rate driven for `--seconds`; prints one row per rate and
the knee (`traffic.knee`) with the fixed rate that follows from it
(`traffic.fixed_rate`: 0.8 of the knee, rounded down to 0.5 req/s)."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from chipbench import run, traffic
    made = run.make_ctx(ROOT, args.workload, args.seed, args.seconds,
                        t_start=time.perf_counter())
    if made is None:
        return 1
    _, driver, ctx = made
    rows = driver.sweep(ctx, [float(r) for r in args.rates.split(",")])
    k = traffic.knee(rows)
    print(json.dumps({"sweep": rows, "knee": k,
                      "fixed_rate": None if k is None
                      else traffic.fixed_rate(k)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
