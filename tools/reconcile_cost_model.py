#!/usr/bin/env python
"""Reconcile the auto-parallel cost model against measured on-chip step
times (VERDICT r3 weak #5: the estimator had never been compared to a
real TPU step; its pruning could discard the TPU-best candidate).

Reads every measured llama record it can find — BENCH_R4_PRE_SWEEP.json,
ONCHIP_R{4,5}.jsonl bench_350m* sections — and
prints, per record, the estimator's step time for the same (model,
batch, seq, 1-chip) point next to the measurement, with BOTH the raw
ratio (uncalibrated hardware ceilings) and the calibrated ratio
(measured efficiency factors from auto_parallel/calibration.json).
With --fit, re-fits compute_efficiency from the latest canonical
bench record and rewrites calibration.json. When batch-scaling
sections exist (bench_350m vs bench_350m_b8), also checks that the
estimator's predicted throughput ORDERING matches the measured one —
the planner decision the estimator must get right. Writes the table to
benchmarks/COST_MODEL_RECONCILE.json. Runs entirely on CPU.
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _records():
    bdir = os.path.join(REPO, "benchmarks")
    for path in (os.path.join(bdir, "BENCH_R4_PRE_SWEEP.json"),):
        try:
            with open(path) as f:
                rec = json.load(f)
            yield os.path.basename(path), rec
        except (OSError, ValueError):
            continue
    for jname in ("ONCHIP_R4.jsonl", "ONCHIP_R5.jsonl"):
        jl = os.path.join(bdir, jname)
        if os.path.exists(jl):
            with open(jl) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("section", "").startswith("bench_350m") \
                            and "value" in rec:
                        yield rec["section"], rec


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.distributed.auto_parallel.cost_model import (
        HardwareSpec, ModelStats, estimate_config_cost)
    from paddle_tpu.models import llama as L

    # v5e single chip (the bench hardware)
    v5e = HardwareSpec(flops_per_sec=197e12)

    def compute_rows():
        rows = []
        seen = set()
        for name, rec in _records():
            metric = rec.get("metric", "")
            if "llama" not in metric or rec.get("extra", {}).get("stale"):
                continue
            ex = rec.get("extra", {})
            knobs = ex.get("bench_knobs") or {}
            if "BENCH_REMAT" in knobs \
                    and knobs["BENCH_REMAT"] not in ("0", ""):
                continue  # remat adds ~1/3 fwd FLOPs estimator ignores
            if ex.get("n_chips", 1) != 1:
                # the estimator below is pinned to the 1-chip config; a
                # multi-chip record folds ICI comm into the ratio
                continue
            if not ex.get("n_params"):
                continue   # can't price a model of unknown size
            sig = (metric, ex.get("batch"), ex.get("seq"),
                   rec.get("value"))
            if sig in seen:
                continue
            seen.add(sig)
            size = "350m" if "350m" in metric else (
                "1b" if "1b" in metric else None)
            if size is None:
                continue
            cfg = {"350m": L.llama_350m, "1b": L.llama_1b}[size]()
            B, S = ex.get("batch", 4), ex.get("seq", 2048)
            stats = ModelStats(
                param_count=ex["n_params"],
                layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
                heads=cfg.num_attention_heads, seq_len=S,
                vocab=cfg.vocab_size)
            cfg1 = dict(dp_degree=1, mp_degree=1, pp_degree=1,
                        sharding_degree=1)
            raw = estimate_config_cost(stats, cfg1, B, v5e,
                                       calibration={})
            cal = estimate_config_cost(stats, cfg1, B, v5e)
            tokens = B * S
            meas_t = tokens / rec["value"]    # s per step per chip
            rows.append({
                "source": name, "model": size, "batch": B, "seq": S,
                "measured_step_s": round(meas_t, 4),
                "estimated_step_s_raw": round(float(raw.step_time_s), 4),
                "ratio_meas_over_est_raw":
                    round(meas_t / float(raw.step_time_s), 3),
                "estimated_step_s_calibrated":
                    round(float(cal.step_time_s), 4),
                "ratio_meas_over_est_calibrated":
                    round(meas_t / float(cal.step_time_s), 3),
                "ablation_flags": ex.get("ablation_flags"),
                "bench_knobs": knobs or None,
            })
        return rows

    rows = compute_rows()

    # --fit: re-fit compute_efficiency from the newest canonical point
    # (no ablation flags, no knobs — the comparable configuration),
    # then RECOMPUTE the rows so the emitted artifact carries post-fit
    # ratios, not the stale pre-fit ones
    if "--fit" in sys.argv:
        canon = [r for r in rows
                 if not r["ablation_flags"] and not r["bench_knobs"]]
        if canon:
            r = canon[-1]
            from paddle_tpu.distributed.auto_parallel import cost_model
            old = cost_model.load_calibration()
            # seed eff with the SAME hw gate the estimator applied when
            # computing the ratio: a calibration recorded for different
            # hardware was ignored there, so the ratio is relative to
            # the raw ceiling, not the file's efficiency
            old_hw = old.get("hw_flops_per_sec")
            gated_out = (old_hw is not None
                         and float(old_hw) != v5e.flops_per_sec)
            eff = (v5e.mfu_ceiling if gated_out
                   else float(old.get("compute_efficiency",
                                      v5e.mfu_ceiling)))
            # est_cal = F/(peak*eff) and ratio = meas/est_cal, so the
            # efficiency that makes est == meas is eff/ratio
            fitted = round(eff / r["ratio_meas_over_est_calibrated"], 4)
            new = dict(old)
            new.update(compute_efficiency=fitted,
                       hw_flops_per_sec=v5e.flops_per_sec,
                       fitted_from=r["source"],
                       operating_point=(f"llama {r['model']} "
                                        f"B={r['batch']} S={r['seq']}, "
                                        "v5e single chip"))
            path = os.path.join(
                REPO, "paddle_tpu", "distributed", "auto_parallel",
                "calibration.json")
            with open(path, "w") as f:
                json.dump(new, f, indent=1)
            print(f"refit compute_efficiency {eff} -> {fitted} "
                  f"from {r['source']}", file=sys.stderr)
            cost_model._CALIBRATION = None     # drop the stale cache
            rows = compute_rows()

    # planner-ordering validation: does the calibrated estimator rank
    # batch-size candidates the way the chip measured them? Session
    # rows carry their jsonl section name as source (bench_350m,
    # bench_350m_b8, ...); only the BENCH_BATCH knob may vary.
    ordering = None
    by_batch = {}
    for r in rows:
        if r["model"] == "350m" and not r["ablation_flags"] \
                and r["source"].startswith("bench_350m") \
                and set(r["bench_knobs"] or {}) <= {"BENCH_BATCH"}:
            by_batch[r["batch"]] = r
    if len(by_batch) >= 2:
        meas_rank = sorted(by_batch, key=lambda b: by_batch[b]
                           ["measured_step_s"] / b)
        est_rank = sorted(by_batch, key=lambda b: by_batch[b]
                          ["estimated_step_s_calibrated"] / b)
        ordering = {"candidates_by_batch": sorted(by_batch),
                    "measured_best_first": meas_rank,
                    "estimated_best_first": est_rank,
                    "confirmed": meas_rank == est_rank}

    out = {"hw": "v5e 197e12 bf16 peak", "rows": rows,
           "planner_ordering": ordering}
    print(json.dumps(out, indent=1))
    if rows:
        with open(os.path.join(REPO, "benchmarks",
                               "COST_MODEL_RECONCILE.json"), "w") as f:
            json.dump(out, f, indent=1)
        print(f"\n{len(rows)} reconciliation points written to "
              "benchmarks/COST_MODEL_RECONCILE.json", file=sys.stderr)
    else:
        print("no non-stale measured llama records found", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
