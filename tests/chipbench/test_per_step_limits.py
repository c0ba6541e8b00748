"""`correct`'s loss gap held a checked step (ISSUE 40): `limits.loss_gap.
limit` as a list gives one row a step, each under a limit of its own; as a
number it gives the one row it always gave. On the CPU, on the tiny
`dots3_note` root, whose cell has the list form as the cell at the
published widths has."""
import contextlib
import io
import json
import os
import shutil
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_dots3")
TINY = "tiny-dots3.pretrain"
sys.path.insert(0, ROOT)

from chipbench import control  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench.drivers import pretrain, train  # noqa: E402

REF = {"losses": [5.5, 5.4, 5.3], "grad_norms": {"a": 1.0, "b": 2.0},
       "delta_norms": {"a": 0.1, "b": 0.2}}


def _ctx(limit):
    cell = {"correct": {"controls": ["fp8"], "limits": {
        "loss_gap": {"limit": limit},
        "first_grad_norm_gap": {"limit": 0.01},
        "param_change_norm_gap": {"limit": 0.01}}}}
    return types.SimpleNamespace(cell=cell, config={"trainer": {"lr": 1.0}})


def _got(*gaps):
    return dict(REF, losses=[a + g for a, g in zip(REF["losses"], gaps)])


def test_a_list_gives_one_row_a_step_each_under_its_own_limit():
    rows = train.compare(_ctx([0.001, 0.01, 0.01]), _got(0.0005, 0.004, -0.009),
                         REF)
    assert [r["name"] for r in rows] == [
        "loss_gap.step1", "loss_gap.step2", "loss_gap.step3",
        "first_grad_norm_gap", "param_change_norm_gap"]
    assert [r["limit"] for r in rows[:3]] == [0.001, 0.01, 0.01]
    assert [r["value"] for r in rows[:3]] == pytest.approx(
        [0.0005, 0.004, 0.009])
    assert all(r["ok"] for r in rows)


@pytest.mark.parametrize("gaps,failed", [
    ((0.004, 0.004, 0.004), "loss_gap.step1"),     # under the others' 0.01
    ((0.0005, 0.012, 0.004), "loss_gap.step2"),
    ((0.0005, 0.004, float("nan")), "loss_gap.step3")])
def test_one_step_over_its_own_limit_is_not_correct(gaps, failed):
    rows = train.compare(_ctx([0.001, 0.01, 0.01]), _got(*gaps), REF)
    assert [r["name"] for r in rows if not r["ok"]] == [failed]


def test_a_number_gives_the_one_row_it_gave():
    got = _got(0.0005, -0.004, 0.002)
    rows = train.compare(_ctx(0.0045), got, REF)
    assert [r["name"] for r in rows] == [
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap"]
    assert rows[0] == {
        "name": "loss_gap", "value": pytest.approx(0.004), "limit": 0.0045,
        "ok": True, "note": f"program {got['losses']} reference "
                            f"{REF['losses']}"}
    assert not train.compare(_ctx(0.0045), _got(0, 0.0046, 0), REF)[0]["ok"]


def _root_with(tmp_path, limit):
    shutil.copytree(DATA, tmp_path / "root")
    path = tmp_path / "root" / "bench" / "cells" / (TINY + ".json")
    cell = json.load(open(path))
    cell["correct"]["limits"]["loss_gap"]["limit"] = limit
    json.dump(cell, open(path, "w"))
    return str(tmp_path / "root")


def test_a_list_of_the_wrong_length_is_an_error_that_names_the_cell(
        tmp_path):
    with pytest.raises(SystemExit) as err:
        bench_run.load_cell(_root_with(tmp_path, [0.001, 0.01]), TINY)
    assert TINY in str(err.value) and "loss_gap" in str(err.value)
    assert "list of 2" in str(err.value) and "is 3" in str(err.value)


def test_the_cells_lists_have_the_length_of_their_checked_steps():
    _, _, cell, _, traffic = bench_run.load_cell(DATA, TINY)
    assert len(cell["correct"]["limits"]["loss_gap"]["limit"]) == traffic[
        "check_steps"] == 3
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    forms = {}
    for w in m["workloads"]:
        _, _, cell, _, traffic = bench_run.load_cell(ROOT, w["name"])
        limit = cell["correct"]["limits"]["loss_gap"]["limit"]
        forms[w["name"]] = len(limit) if isinstance(limit, list) else None
        assert forms[w["name"]] in (None, traffic["check_steps"]), w["name"]
    # the four older cells keep the number, and print what they printed;
    # by name: a later cell has either form, and changes nothing here
    accepted = {
        "yi-6b-1chip.pretrain": None, "yi-6b-4chip.pretrain": None,
        "solar-open2-250b-ep40.pretrain-32k": None,
        "granite-4.0-h-micro-pp4.pretrain-32k": None,
        "dots3-note-prev-ep32.pretrain-16k": 2}
    assert {k: v for k, v in forms.items() if k in accepted} == accepted


@pytest.mark.parametrize("limit", [[0.001, 0.01, 0.01], 0.0045])
def test_control_py_reads_every_step_whatever_the_limits_form(
        tmp_path, monkeypatch, limit):
    """The driver's `control` with the model taken out: sound, control
    and the cell's gross faults, the summary by the rows' names."""
    asked = []

    def fake(ctx, controls=True, faults=False):
        def follow(mode=None, trainer=ctx.config["trainer"]):
            asked.append((mode, trainer["learning_rate"]))
            if mode:
                return _got(0.002, 0.003, 0.004)
            if trainer is not ctx.config["trainer"]:
                return _got(0.0, 0.02, 0.03)
            return REF
        return train.control_sides(ctx, _got(0.0005, 0.004, -0.009), follow,
                                   controls, faults)

    monkeypatch.setattr(pretrain, "control", fake)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = control.main(
            ["--workload", TINY, "--seeds", "5,6", "--controls", "5",
             "--faults", "6"], root=_root_with(tmp_path, limit),
            require_chip=False)
    assert rc == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines()
             if x.startswith("{")]
    five, six, summary = lines[0], lines[1], lines[2]["summary"]
    steps = ["loss_gap.step1", "loss_gap.step2", "loss_gap.step3"]
    assert set(five) == {"seed", "sound", "fp8", "notes"}
    assert set(six) == {"seed", "sound", "fault:update_not_applied",
                        "fault:learning_rate_doubled", "notes"}
    assert asked == [(None, 0.0003), ("fp8", 0.0003),
                     (None, 0.0003), (None, 0.0), (None, 0.0006)]
    for side in (five["sound"], five["fp8"], six["fault:update_not_applied"]):
        assert [n for n in side if n.startswith("loss_gap.")] == steps
        assert ("loss_gap" in side) == (not isinstance(limit, list))
    assert len(five["notes"]["sound"]) == 3        # the losses once
    assert "program [" in list(five["notes"]["sound"].values())[0]
    assert [summary[n]["sound_largest"] for n in steps] == pytest.approx(
        [0.0005, 0.004, 0.009])
    assert [summary[n]["fp8_smallest"] for n in steps] == pytest.approx(
        [0.002, 0.003, 0.004])
    assert summary["loss_gap.step2"][
        "fault:update_not_applied_smallest"] == pytest.approx(0.02)


def test_a_run_carries_the_compared_numbers_last_in_its_line(monkeypatch):
    """What the record of a refused run keeps is the end of its last
    line: each number compared and its limit are there, a step a row."""
    def fake_run(ctx):
        rows = train.compare(ctx, _got(0.0005, 0.004, float("inf")), REF)
        return {"end_to_end": {"setup_s": 1.0,
                               "train_tokens_per_s_chip": 2.0},
                "attempted": 3, "failed": 0, "compared": rows,
                "peak_bytes": None, "run": {}}

    monkeypatch.setattr(pretrain, "run", fake_run)
    out = bench_run.run_cell(DATA, TINY, 3, 0.1, False, require_chip=False,
                             t_start=time.perf_counter())
    assert list(out)[-1] == "compared" and out["correct"] is False
    assert list(out["compared"]) == [
        "loss_gap.step1", "loss_gap.step2", "loss_gap.step3",
        "first_grad_norm_gap", "param_change_norm_gap"]
    assert out["compared"]["loss_gap.step2"] == {
        "value": pytest.approx(0.004), "limit": 0.0012}
    assert out["compared"]["loss_gap.step3"]["value"] == "inf"
    json.loads(json.dumps(out), parse_constant=lambda c: 1 / 0)
