"""The readers of the program's own names (PR 25): `scope_reduce` over
`components.json`, `costs_components`, and every per-layer metric that
PR added, on a recorded chip trace WITH op_names and the program's spans
(two steps of `yi-6b-1chip.pretrain`, my chip run, PR 25), on PR 24's
recorded trace, which has neither, and on a hand-written four-device
window for the collective classes."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
sys.path.insert(0, ROOT)

from chipbench import costs, costs_components, scope_reduce  # noqa: E402
from chipbench import run as bench_run  # noqa: E402

PEAKS = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
    "TPU v5 lite"]
YI = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                 "yi-6b-1chip.json")))
ACCEPTED = {"device_idle_share.train", "train_mfu", "trace_lower_s",
            "collective_exposed_share", "flash_attention_roofline"}
# read from set-up events younger than `data/ring_setup_small.json`: the
# ring of `test_step_memory_readers.py` has them (every cell lists them
# since ISSUE 40)
ACCEPTED_ON_ANOTHER_RING = {"step_hbm_peak_bytes", "step_temp_bytes",
                            "kept_residual_bytes"}


def _new_entries(accepted=ACCEPTED):
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return [x for x in m["per_layer"] if x["name"] not in accepted]


def _run(trace, steps=2, chips=1, config=YI):
    """What the train driver hands the readers, with a trace that is
    already reduced (no xplane to load)."""
    return {"kind": "train", "chips": chips, "steps_traced": steps,
            "peaks": PEAKS, "config": config, "batch_size": chips // 2 or 1,
            "seq_len": 4096, "lower_s": 10.823,
            "trace": {"dir": None, "scope_reduced": (
                scope_reduce.reduce(trace) if trace else None)}}


@pytest.fixture(scope="module")
def recorded():
    return json.load(open(os.path.join(DATA, "trace_scopes_small.json")))


@pytest.fixture(scope="module")
def unnamed():
    """PR 24's recorded trace: instruction names only, chipbench's span."""
    old = json.load(open(os.path.join(DATA, "trace_small.json")))
    return {"device": {p: [[n, s, d, None] for n, s, d in ev]
                       for p, ev in old["device"].items()},
            "spans": old["spans"]}


def test_names_resolve_by_scope_kernel_and_direction():
    t = scope_reduce.rules()
    r = scope_reduce.resolve
    layer = ("jit(pure)/backward/transpose(jvp(layers))/while/body/"
             "closed_call/checkpoint/")
    assert r("fusion.276", layer + "LlamaDecoderLayer/mlp/mlp/dot_general",
             t) == ("mlp", "backward", "backward")
    assert r("rms_norm.17", layer + "rematted_computation/LlamaDecoderLayer/"
             "input_layernorm/norm/rms_norm/pallas_call:", t) == (
        "norm", "recomputed", "backward")
    assert r("subtract_convert_fusion.1",
             "jit(pure)/backward/transpose(jvp(head))/dot_general", t)[:2] == (
        "head", "backward")
    assert r("subtract_convert_fusion.9",
             "jit(pure)/optimizer/convert_element_type", t) == (
        "optimizer", "update", "optimizer")
    assert r("fusion.217", "jit(pure)/backward/transpose(jvp(layers))/split",
             t)[:2] == ("layers", "backward")
    assert r("fusion.97", "jit(pure)/forward/loss/jvp(loss)/jit(log_softmax)"
             "/sub", t) == ("loss", "forward", "forward")
    # a Mosaic call without op_name: told by its kernel; forward work
    # inside the backward `while` is recomputation
    assert r("splash_mqa_fwd_residuals.15", None, t, "backward") == (
        "attn/core", "recomputed", "backward")
    assert r("splash_mqa_dkv_no_residuals.9", None, t, "backward")[:2] == (
        "attn/core", "backward")
    assert r("swiglu_fwd.6", None, t, "forward")[:2] == ("mlp", "forward")
    assert r("copy-done.12", None, t, "forward")[0] is None


def test_collectives_are_classed_by_scope_opcode_and_phase():
    t = scope_reduce.rules()
    c = scope_reduce.collective_class
    mlp = "jit(pure)/forward/LM/model/jvp(layers)/while/body/DL/mlp/mlp/"
    assert c("psum.3", "jit(pure)/backward/transpose(jvp(layers))/x/mlp/mlp/"
             "tp/all_reduce/shard_map/psum", t, "backward") == "tp_all_reduce"
    assert c("all-reduce.7", mlp + "tp/all_reduce/dot_general", t,
             "forward") == "tp_all_reduce"
    assert c("all-gather.2", mlp + "tp/all_reduce/dot_general", t,
             "forward") == "zero3"             # a parameter's gather
    assert c("all-to-all.1", mlp + "tp/relayout/reshape", t,
             "forward") == "tp_relayout"
    assert c("copy.44", mlp + "tp/relayout/reshape", t,
             "forward") == "tp_relayout_copy"
    assert c("all-reduce.9", "jit(pure)/backward/transpose(jvp(embed))/x", t,
             "backward") == "zero3"            # the gradient's
    assert c("all-reduce.1", "jit(pure)/forward/loss/jvp(loss)/reduce_max", t,
             "forward") == "other"
    assert c("copy.1", mlp + "dot_general", t, "forward") is None
    assert c("fusion.3", mlp + "dot_general", t, "forward") is None


def test_reduction_of_the_recorded_chip_trace(recorded):
    red = scope_reduce.reduce(recorded)
    assert red["has_op_names"] and red["n_devices"] == 1
    steps = 2
    per_step = {g: scope_reduce.group_s(red, g) * 1e3 / steps
                for g in ("attention", "mlp", "head_loss", "optimizer")}
    # my chip run, PR 25: mlp 102.4, attention 56.2, head+loss 42.9,
    # optimizer 19.7 ms a step
    assert 95 < per_step["mlp"] < 110 and 50 < per_step["attention"] < 62
    assert 38 < per_step["head_loss"] < 48 and 17 < per_step["optimizer"] < 23
    assert red["named_s"] / red["busy_s"] > 0.99
    # everything adds up to the busy time: groups, the other named
    # components, collectives (none on one chip), the unnamed rest
    grouped = sum(scope_reduce.group_s(red, g) for g in per_step)
    other = sum(s for (c, _), s in red["component_s"].items()
                if c in ("embed", "layers", "norm"))
    total = grouped + other + sum(red["collective_s"].values()) + \
        red["unnamed_total_s"]
    assert total == pytest.approx(red["busy_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.4967, rel=0.01)
    recomputed = sum(s for (c, d), s in red["component_s"].items()
                     if d == "recomputed")
    assert 0.015 < recomputed < 0.025            # ~9.6 ms a step
    assert not any(d == "recomputed" for (c, d) in red["component_s"]
                   if c in ("head", "loss", "optimizer"))
    # the program's three spans lie inside chipbench's `train_step`, and
    # every idle gap is named by one of the four
    assert {k: len(v) for k, v in red["host_span_s"].items()} == {
        "train_step.call_args": 2, "train_step.dispatch": 2,
        "train_step.write_back": 2}
    assert {k for k, _ in red["idle_by_span_s"]} <= set(
        scope_reduce.SPAN_NAMES)


def test_every_new_reader_on_the_recorded_chip_trace(recorded, monkeypatch):
    ring = json.load(open(os.path.join(DATA, "ring_setup_small.json")))
    monkeypatch.setattr(scope_reduce, "setup_phases",
                        lambda r=None, _f=scope_reduce.setup_phases: _f(ring))
    run = _run(recorded)
    got = {}
    for x in _new_entries(ACCEPTED | ACCEPTED_ON_ANOTHER_RING):
        if "yi-6b-1chip.pretrain" not in x["workloads"]:
            continue
        value = bench_run.layer_metric(x["name"]).compute(run)
        assert value is not None, x["name"]
        value, note = value
        assert isinstance(note, str) and note
        got[x["name"]] = value
    assert got["scope_coverage"] > 99
    assert 0 < got["mlp_roofline"] <= 100 and 0 < got["head_loss_roofline"] <= 100
    assert got["mlp_roofline"] == pytest.approx(65.9, abs=2)
    assert got["head_loss_roofline"] == pytest.approx(76.2, abs=2)
    assert got["remat_recompute_ms_per_step"] == pytest.approx(9.6, abs=1)
    assert got["step_host_ms"] == pytest.approx(0.5, abs=0.2)
    assert got["train_step_retraces"] == 0
    # the five parts of lower() and the printed rest add up to the span,
    # which is chipbench's own trace_lower_s (10.823 s in that run)
    ph = scope_reduce.setup_phases()
    assert ph["lower"] == pytest.approx(10.823, abs=0.01)
    assert got["lower_inner_compile_s"] == pytest.approx(6.74, abs=0.05)
    assert ph["inner_compiles"] == 74
    parts = (got["lower_forward_s"] + got["lower_backward_s"]
             + got["lower_optimizer_s"] + got["lower_to_mlir_s"]
             + got["lower_inner_compile_s"] + ph["call_args"]
             + ph["inner_to_mlir"] + ph["grad_sync"] + ph["rest"])
    assert parts == pytest.approx(ph["lower"], rel=1e-6)
    assert abs(ph["rest"]) < 0.05 * ph["lower"]


def test_readers_on_a_trace_without_names(unnamed, monkeypatch):
    """PR 24's trace, as a program that names nothing gives it: every
    reader returns None and does not raise; scope_coverage reads 0."""
    monkeypatch.setattr(scope_reduce, "setup_phases", lambda r=None: None)
    run = _run(unnamed, steps=2)
    assert run["trace"]["scope_reduced"]["has_op_names"] is False
    for x in _new_entries():
        value = bench_run.layer_metric(x["name"]).compute(run)
        if x["name"] == "scope_coverage":
            assert value[0] == 0.0
        else:
            assert value is None, x["name"]
    # and with no trace at all (an untraced run never calls a reader,
    # but a reader must not mind)
    bare = _run(None)
    bare["trace"] = None
    for x in _new_entries():
        assert bench_run.layer_metric(x["name"]).compute(bare) is None


def test_collective_shares_on_a_four_device_window():
    mlp = ("jit(pure)/forward/LM/model/jvp(layers)/while/body/closed_call/"
           "LlamaDecoderLayer/mlp/mlp/")
    ms = 1_000_000
    line = [["fusion.1", 0, 40 * ms, mlp + "dot_general"],
            ["all-reduce.1", 40 * ms, 10 * ms, mlp + "tp/all_reduce/dot_general"],
            ["all-to-all.1", 50 * ms, 6 * ms, mlp + "tp/relayout/reshape"],
            ["copy.9", 56 * ms, 2 * ms, mlp + "tp/relayout/reshape"],
            ["all-gather.1", 58 * ms, 3 * ms, mlp + "tp/all_reduce/dot_general"],
            ["psum.4", 61 * ms, 8 * ms, "jit(pure)/backward/transpose(jvp("
             "layers))/x/norm/tp/all_reduce/shard_map/psum"],
            ["all-reduce.2", 69 * ms, 1 * ms,
             "jit(pure)/forward/loss/jvp(loss)/reduce_max"],
            ["fusion.2", 70 * ms, 30 * ms, "jit(pure)/optimizer/sub"]]
    trace = {"device": {f"/device:TPU:{i}": line for i in range(4)},
             "spans": [["train_step", 0, 100 * ms]]}
    run = _run(trace, steps=1, chips=4)
    share = {n: bench_run.layer_metric(n).compute(run)[0] for n in (
        "zero3_exposed_share", "tp_allreduce_exposed_share",
        "tp_relayout_exposed_share")}
    assert share == {"zero3_exposed_share": pytest.approx(3.0),
                     "tp_allreduce_exposed_share": pytest.approx(18.0),
                     "tp_relayout_exposed_share": pytest.approx(8.0)}
    note = bench_run.layer_metric("zero3_exposed_share").compute(run)[1]
    assert "sum=28.00%" in note and "other collectives=1.00%" in note
    # compute time does not hold the collectives; on one chip no share
    assert bench_run.layer_metric("mlp_ms_per_step").compute(run)[0] == \
        pytest.approx(40.0)
    assert bench_run.layer_metric("zero3_exposed_share").compute(
        _run(trace, steps=1, chips=1)) is None


def test_costs_of_the_mlp_and_the_head():
    t = 4096
    flops, byts = costs_components.mlp_train(YI, 1, t)
    assert flops == 18 * t * 4096 * 11008 + 14 * t * 11008
    assert byts == 2 * (5 * t * 4096 + 9 * 4096 * 11008)
    assert costs.roofline_s(flops, byts, PEAKS) == (
        pytest.approx(flops / 197e12), "compute")
    flops, byts = costs_components.head_loss_train(YI, 1, t)
    assert flops == 6 * t * 4096 * 64000 + 8 * t * 64000
    assert costs.roofline_s(flops, byts, PEAKS)[1] == "compute"
    # together with attention and the projections they stay below the
    # whole step's count (costs.train_flops_per_token)
    mlp4 = 4 * costs_components.mlp_train(YI, 1, t)[0]
    assert mlp4 + flops < costs.train_flops_per_token(YI, t) * t


@pytest.mark.parametrize("entry", _new_entries(), ids=lambda x: x["name"])
def test_manifest_entry_matches_its_reader(entry):
    mod = bench_run.layer_metric(entry["name"])
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter")
    assert mod.__doc__ and len(mod.__doc__) > 60
    assert ("roofline" in entry["name"]) == entry["name"].endswith("_roofline")


def test_xplane_fields_and_hlo_names_by_hand():
    """The hand-read protobuf: a tiny XSpace with one device plane whose
    event metadata carries `tf_op`, and a metadata plane with an HLO
    module."""
    def varint(n):
        out = bytearray()
        while True:
            b = n & 0x7F
            n >>= 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)

    def field(num, payload):
        if isinstance(payload, int):
            return varint(num << 3) + varint(payload)
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    stat_meta = field(5, field(1, 7) + field(2, field(1, 7)
                                             + field(2, b"tf_op")))
    stat = field(5, field(1, 7) + field(5, b"jit(pure)/forward/x/mlp/dot:"))
    ev_meta = field(4, field(1, 3) + field(2, field(1, 3) + field(
        2, b"%fusion.1 = bf16[8] fusion()") + stat))
    plane = field(1, field(2, b"/device:TPU:0") + stat_meta + ev_meta)
    strings = scope_reduce.metadata_strings(plane)
    assert strings == {"%fusion.1 = bf16[8] fusion()": {
        "tf_op": "jit(pure)/forward/x/mlp/dot:"}}
    assert scope_reduce._op_name_in(
        "%fusion.1 = bf16[8] fusion()",
        strings["%fusion.1 = bf16[8] fusion()"]).endswith("mlp/dot:")
    instr = field(1, b"fusion.2") + field(2, b"fusion") + field(
        7, field(2, b"jit(pure)/optimizer/sub"))
    proto = field(1, field(1, b"jit_pure") + field(3, field(
        1, b"main") + field(2, instr)))
    meta_plane = field(1, field(2, b"/host:metadata") + field(4, field(
        1, 1) + field(2, field(1, 1) + field(5, field(1, 9)
                                             + field(6, proto)))))
    assert scope_reduce.hlo_op_names(plane + meta_plane) == {
        "fusion.2": "jit(pure)/optimizer/sub"}
