"""The `xing4_0` cell at tiny widths THROUGH the `pretrain` driver (ISSUE 48;
a file of its own beside `test_xing4_0_cell.py`: a test file is one worker's,
and each of these compiles the tiny step): a run end to end through the data
files of `data_xing/`, `correct` seen to fail under the control and under the
cell's four faults (Sinkhorn cut short among them, a model key read from the
trainer settings), and the by-hand tool that reads the step's counter of
H_res's sums beside the reference's on the same step."""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_xing4_0_cell import (DATA, FAULTS, TINY, _ctx,  # noqa: E402
                               bench_run, pretrain)


def test_cell_end_to_end_on_the_cpu():
    out = bench_run.run_cell(DATA, TINY, 2147483693, 0.5, False,
                             require_chip=False, t_start=time.perf_counter())
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["compared"]) >= {"loss_gap.step1", "loss_gap.step2",
                                    "moe_dropped_pairs"}
    json.dumps(out)


def test_the_control_and_the_four_faults_each_fail_a_limit():
    """fp8 in the program's place, an update not applied, a doubled
    learning rate, the module's loss left out, and Sinkhorn cut short
    (`hc_sinkhorn_iters` 1 in the trainer's settings: H_res's rows then sum
    to 1 to tenths only, and every half-layer's output moves)."""
    out = pretrain.control(_ctx(seed=11), controls=True, faults=True)
    assert all(r["ok"] for r in out["sound"]), out["sound"]
    assert set(out) == {"sound", "fp8"} | {"fault:" + f for f in FAULTS}
    for side in set(out) - {"sound"}:
        assert not all(r["ok"] for r in out[side]), (side, out[side])
    left = {r["name"]: r for r in out["fault:mtp_loss_left_out"]}
    assert left["loss_gap.step1"]["value"] > 1.0          # 0.3 x ~5.5
    assert left["first_grad_norm_gap"]["value"] == pytest.approx(1.0)



def test_the_same_step_tool_reads_both_sides_of_the_counter(capsys):
    """`chipbench/hc_same_step.py` on the tiny root: the step's counter after
    its first step beside the reference's on the same step."""
    from chipbench import hc_same_step
    assert hc_same_step.main(["--workload", TINY, "--seed", "13", "--steps",
                              "1"], root=DATA, require_chip=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(out["program_hc_res_sum_err"]) == 1
    assert max(out["row_err_gap"]) < 1e-5
    for side in ("program_hc_res_sum_err", "reference_hc_res_sum_err"):
        assert all(0 <= cols < 1e-5 for _, cols in out[side])
    assert out["program_losses"] == pytest.approx(out["reference_losses"],
                                                  abs=2e-3)
