"""The three readers of the compiled step's memory events (PR 36), on a
recorded ring (`data/ring_memory_small.json`: the events the
`yi-6b-4chip.pretrain` step leaves when it is lowered and compiled at its
real size for a described v5e:2x2, sandbox compile, PR 36: no
`bytes_limit`, which only a device that is attached has) and on PR 25's
recorded ring, which has none of them: what a tree before PR 36 gives."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402
from chipbench import step_memory  # noqa: E402

NAMES = ("step_hbm_peak_bytes", "step_temp_bytes", "kept_residual_bytes")
RUN = {"kind": "train", "chips": 4}


@pytest.fixture
def recorded(monkeypatch):
    ring = json.load(open(os.path.join(DATA, "ring_memory_small.json")))
    monkeypatch.setattr(step_memory, "_ring", lambda ring_=None: ring)
    return ring


@pytest.fixture
def before(monkeypatch):
    ring = json.load(open(os.path.join(DATA, "ring_setup_small.json")))
    monkeypatch.setattr(step_memory, "_ring", lambda ring_=None: ring)
    return ring


def test_the_peak_is_the_compilers_own_under_the_terms_sum(recorded):
    mem = step_memory.memory()
    assert mem["sum_bytes"] == (
        mem["argument_bytes"] + mem["output_bytes"] - mem["alias_bytes"]
        + mem["temp_bytes"] + mem["generated_code_bytes"])
    assert mem["argument_bytes"] < mem["peak_bytes"] < mem["sum_bytes"]
    value, note = bench_run.layer_metric("step_hbm_peak_bytes").compute(RUN)
    assert value == mem["peak_bytes"]
    for term in ("argument_bytes=", "output_bytes=", "alias_bytes=",
                 "temp_bytes=", "generated_code_bytes=", "sum_bytes=",
                 "devices=4", "read_s=", "peak_bytes_in_use="):
        assert term in note, term
    value, note = bench_run.layer_metric("step_temp_bytes").compute(RUN)
    assert value == mem["temp_bytes"] and str(mem["peak_bytes"]) in note
    assert str(mem["peak_bytes"] - mem["argument_bytes"]) in note


def test_headroom_is_the_devices_limit_less_the_peak(recorded, monkeypatch):
    limit = 16911433728                      # 15.75 GiB
    ring = [dict(ev, attrs=dict(ev["attrs"], bytes_limit=str(limit)))
            if ev["name"] == "train_step.memory" else ev for ev in recorded]
    monkeypatch.setattr(step_memory, "_ring", lambda ring_=None: ring)
    mem = step_memory.memory()
    _, note = bench_run.layer_metric("step_hbm_peak_bytes").compute(RUN)
    assert f"bytes_limit={limit}" in note
    assert f"headroom={limit - mem['peak_bytes']}" in note


def test_the_ledger_lists_every_scope_largest_first(recorded):
    total, rows = step_memory.residuals()
    assert total["trace"] == 1 and total["shapes"] == "global"
    assert sum(b for _, b, _ in rows) == total["bytes"]
    assert [b for _, b, _ in rows] == sorted((b for _, b, _ in rows),
                                             reverse=True)
    value, note = bench_run.layer_metric("kept_residual_bytes").compute(RUN)
    assert value == total["bytes"] > 0
    scopes = [part.split("=")[0] for part in note.split(" | ")[0].split(", ")]
    assert scopes == [s for s, _, _ in rows] and "decoder_scan" in scopes
    assert f"state_bytes={total['state_bytes']}" in note
    assert "% of temp" in note and "walk_s=" in note
    # train_step.kept: name x calls x bytes of one call
    kept = step_memory.kept()
    assert kept and all(f"{k} x {n} x {b}" in note
                        for k, (n, b) in kept.items())
    # the share is of the temporaries of ALL devices: the ledger's shapes
    # are the whole program's
    mem = step_memory.memory()
    assert f"temp_bytes={mem['temp_bytes']} x {mem['devices']}" in note


def test_a_retrace_does_not_change_what_the_first_trace_left(recorded):
    again = [dict(ev, attrs=dict(ev["attrs"], trace="2", bytes="1"))
             for ev in recorded if ev["name"] == "train_step.residuals"]
    first = step_memory.residuals(recorded)
    assert step_memory.residuals(recorded + again) == first


@pytest.mark.parametrize("name", NAMES)
def test_readers_return_nothing_where_the_program_records_nothing(
        name, before):
    assert any(ev["name"] == "train_step.traced" for ev in before)
    assert bench_run.layer_metric(name).compute(RUN) is None
    assert bench_run.layer_metric(name).compute(dict(RUN, trace=None)) is None


@pytest.mark.parametrize("name", NAMES)
def test_readers_see_the_live_ring_only_where_a_trace_is_on_record(
        name, monkeypatch):
    """A process whose earlier work left events in the live ring: where
    `setup_phases` finds no trace of a step (`test_scope_metrics.py`
    patches it so), these readers find nothing either."""
    from chipbench import scope_reduce
    from paddle_tpu.observability import spans
    for ev in json.load(open(os.path.join(DATA, "ring_memory_small.json"))):
        spans.setup_event(ev["name"], dur_s=ev.get("dur_s"), **ev["attrs"])
    try:
        assert bench_run.layer_metric(name).compute(RUN) is not None
        monkeypatch.setattr(scope_reduce, "setup_phases", lambda r=None: None)
        assert bench_run.layer_metric(name).compute(RUN) is None
    finally:
        spans.clear()


@pytest.mark.parametrize("name", NAMES)
def test_readers_leave_a_serving_run_alone(name, recorded):
    assert bench_run.layer_metric(name).compute({"kind": "serve",
                                                 "chips": 1}) is None


def test_the_ledger_alone_still_reports(recorded, monkeypatch):
    """`lower()` without `compile()` leaves the ledger and no count."""
    only = [ev for ev in recorded if ev["name"] != "train_step.memory"]
    monkeypatch.setattr(step_memory, "_ring", lambda ring_=None: only)
    assert bench_run.layer_metric("step_temp_bytes").compute(RUN) is None
    value, note = bench_run.layer_metric("kept_residual_bytes").compute(RUN)
    assert value > 0 and "% of temp" not in note


def test_manifest_lists_the_three_under_the_compiled_step():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = {x["name"]: x for x in m["per_layer"] if x["name"] in NAMES}
    assert set(mine) == set(NAMES)
    for x in mine.values():
        assert (x["unit"], x["better"], x["source"], x["layer"],
                x["moves"]) == ("bytes", "lower", "program_counter",
                                "compiled step", "train_tokens_per_s_chip")
        # membership only, and by name: later metrics and cells append
        # themselves (every cell of ISSUE 40's manifest is listed)
        assert {"yi-6b-4chip.pretrain", "yi-6b-1chip.pretrain",
                "dots3-note-prev-ep32.pretrain-16k"} <= set(x["workloads"])
