#!/usr/bin/env python3
"""By hand and not in any run, for a cell whose program counts
`hc_res_sum_err` (the four-stream residual path):

    python3 chipbench/hc_same_step.py --workload <cell> --seed <n> [--steps 2]

The compiled step's counter after each of its first steps (the largest
|row sum - 1| and |column sum - 1| of H_res over that step's tokens and
half-layers) beside the reference's own maps' value on the SAME step, which
no run shows: a run reads the counter after its window's last step and the
reference follows the checked steps only. One JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, root=ROOT, require_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from chipbench import harness, run
    from chipbench.drivers import pretrain
    made = run.make_ctx(root, args.workload, args.seed, 5.0,
                        require_chip=require_chip,
                        t_start=time.perf_counter())
    if made is None:
        return 1
    ctx = made[2]
    program, reference, _ = pretrain.parts(ctx.config)
    sut = pretrain.build(ctx)
    got = []
    for i in range(args.steps):
        loss = float(sut["call"](i))
        got.append((loss, program.counters(sut["model"])["hc_res_sum_err"]))
    make_state, ids = sut["make_state"], sut["ids"]
    sut["reference_warm"].join()
    sut.clear()
    harness.release()
    ref = reference.train_steps(lambda: make_state(args.seed),
                                ids[:args.steps], ctx.config,
                                ctx.config["trainer"])
    print(json.dumps({
        "seed": args.seed, "program_losses": [g[0] for g in got],
        "program_hc_res_sum_err": [g[1] for g in got],
        "reference_losses": ref["losses"],
        "reference_hc_res_sum_err": ref["hc_res_sum_err"],
        "row_err_gap": [abs(g[1][0] - r[0]) for g, r in
                        zip(got, ref["hc_res_sum_err"])]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
