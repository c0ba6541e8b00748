"""The harness takes a cell after the fifth with no edit to an accepted
file (ISSUE 40): the manifest checks of every cell's tests are functions
of a loaded manifest and its root, and pass on the benchmark's own
manifest with a sixth configuration and cell appended in memory; the
readers that several architectures share find their table and cost module
by the run's `model_type` and read the accepted cells as they did."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import test_chipbench as every  # noqa: E402
import test_dots3_note_cell as dots3  # noqa: E402
import test_granite_cell as granite  # noqa: E402
import test_solar_open2_cell as solar  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import scope_reduce, scope_tables  # noqa: E402

SIXTH = "a-sixth-config.pretrain-32k"


def _with_a_sixth(tmp_path):
    """(manifest, root): the benchmark's manifest with the Granite
    configuration and cell appended under other names, the cell listed
    wherever Granite's is, and a root that holds the data files."""
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = dict(next(w for w in m["workloads"] if w["name"] == granite.CELL))
    config = dict(next(c for c in m["configs"]
                       if c["name"] == cell["config"]))
    for part in ("cells", "configs", "traffic"):
        shutil.copytree(os.path.join(ROOT, "chipbench", part),
                        tmp_path / "chipbench" / part)
    shutil.copy(os.path.join(ROOT, config["file"]),
                tmp_path / "chipbench" / "configs" / "a-sixth-config.json")
    shutil.copy(
        os.path.join(ROOT, "chipbench", "cells", granite.CELL + ".json"),
        tmp_path / "chipbench" / "cells" / (SIXTH + ".json"))
    config.update(name="a-sixth-config",
                  file="chipbench/configs/a-sixth-config.json")
    cell.update(name=SIXTH, config="a-sixth-config")
    m["configs"].append(config)
    m["workloads"].append(cell)
    for x in m["end_to_end"] + m["per_layer"]:
        if granite.CELL in x.get("workloads", []):
            x["workloads"].append(SIXTH)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    return m, str(tmp_path)


def test_a_sixth_configuration_and_cell_pass_every_manifest_check(tmp_path):
    m, root = _with_a_sixth(tmp_path)
    before = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert {w["name"] for w in m["workloads"]} == {SIXTH} | {
        w["name"] for w in before["workloads"]}
    assert {c["name"] for c in m["configs"]} == {"a-sixth-config"} | {
        c["name"] for c in before["configs"]}
    every.check_manifest(m, root, whole=True)
    for cell_tests in (granite, solar, dots3):
        cell_tests.check_manifest(m, root)
    # the new cell is a cell like any other: found by name, its files
    # loaded, every metric that lists it read by a reader that is there
    _, entry, cell_file, config, traffic = bench_run.load_cell(root, SIXTH)
    assert entry["config"] == "a-sixth-config" and traffic["seq_len"] == 32768
    assert config["model_type"] == "granitemoehybrid"
    listed = bench_run.metrics_of(m, "per_layer", SIXTH)
    assert {x["name"] for x in listed} >= set(granite.NEW) | set(granite.OLD)


def _runs():
    trace, counters = solar._hand_written()
    return {"solar_open2": solar._run(trace, counters),
            "granitemoehybrid": granite._run(granite._recorded()),
            "dots3_note": dots3._run(
                dots3._recorded(),
                counters={"expert_tokens": [[500, 524, 0, 512]] * 4,
                          "dropped_pairs": 0, "attended_pairs": [1]})}


# (reader, architecture) -> the table, group and cost module the PARENT's
# reader named in its own text
PARENT = {
    ("ssm_ms_per_step", "granitemoehybrid"): (
        "components_granitemoehybrid.json", "ssm", None),
    ("ssd_core_roofline", "granitemoehybrid"): (
        "components_granitemoehybrid.json", "ssd_core", "ssd_core_train"),
    ("ssm_conv_roofline", "granitemoehybrid"): (
        "components_granitemoehybrid.json", "ssm_conv", "ssm_conv_train"),
    ("moe_ms_per_step", "solar_open2"): (
        "components_solar_open2.json", "moe", None),
    ("moe_ms_per_step", "dots3_note"): (
        "components_solar_open2.json", "moe", None),
    ("moe_experts_roofline", "solar_open2"): (
        "components_solar_open2.json", "moe_experts", "moe_experts_train"),
}


@pytest.mark.parametrize("name,kind", sorted(PARENT))
def test_shared_readers_read_the_accepted_cells_as_the_parent_did(name, kind):
    """To the last digit: the value of the re-pointed reader is the value
    under the table and cost function the parent's reader named."""
    run = _runs()[kind]
    table, group, cost = PARENT[name, kind]
    value, note = bench_run.layer_metric(name).compute(run)
    if cost is None:
        want = scope_tables.ms_per_step(_runs()[kind], table, group)[0]
    else:
        mod = __import__("chipbench.costs_" + kind, fromlist=["x"])
        steps, cfg = run["steps_traced"], run["config"]
        if "moe" in name:
            work = [getattr(mod, cost)(cfg, sum(layer))
                    for layer in run["counters"]["expert_tokens"]]
        else:
            work = [getattr(mod, cost)(cfg, run["batch_size"],
                                       run["seq_len"])
                    ] * mod.sizes(cfg)["mamba"]
        want = scope_tables.roofline(
            _runs()[kind], table, group, steps * sum(w[0] for w in work),
            steps * sum(w[1] for w in work), "")[0]
    assert value == want and value > 0
    assert isinstance(note, str) and note


def test_readers_take_table_and_costs_from_the_runs_model_type():
    runs = _runs()
    first = "components_solar_open2.json"
    # a table of the run's own with the group: read through it
    assert scope_tables.table_of(runs["dots3_note"], "moe", first) == \
        "components_dots3_note.json"
    # the run's table lacks the group, or there is no such table: today's
    assert scope_tables.table_of(runs["dots3_note"], "moe_experts",
                                 first) == first
    assert scope_tables.table_of({"config": {"model_type": "llama"}},
                                 "moe", first) == first
    assert scope_tables.table_of({"config": None}, "moe", first) == first
    from chipbench import costs_dots3_note, costs_solar_open2
    assert scope_tables.costs_of(runs["dots3_note"], "moe_experts_train",
                                 "costs_solar_open2") is costs_dots3_note
    assert scope_tables.costs_of(runs["dots3_note"], "kda_core_train",
                                 "costs_solar_open2") is costs_solar_open2
    assert scope_tables.costs_of({"config": {"model_type": "llama"}},
                                 "ssd_core_train", "costs_granitemoehybrid"
                                 ).__name__.endswith("granitemoehybrid")
    # an architecture that brings no count of the state-space core
    for name in ("ssd_core_roofline", "ssm_conv_roofline"):
        assert bench_run.layer_metric(name).compute(
            runs["dots3_note"]) is None


def test_the_experts_roofline_reads_the_latent_attention_cell():
    """18 x pairs x H x M at H 5120, M 1536 and its bytes, through the
    run's own cost module; the device time through Solar's table, whose
    group `moe_experts` the run's own table lacks."""
    run = _runs()["dots3_note"]
    pairs = 500 + 524 + 512
    from chipbench import costs, costs_dots3_note as cd
    flops, byts = cd.moe_experts_train(run["config"], pairs)
    assert flops == 18 * pairs * 5120 * 1536
    assert byts == 2 * (9 * 8 * 5120 * 1536 + 5 * pairs * 5120)
    least, bound = costs.roofline_s(4 * flops, 4 * byts, run["peaks"])
    red, table = scope_tables.reduced(run, "components_solar_open2.json")
    spent = scope_reduce.group_s(red, "moe_experts", None, table)
    assert spent == pytest.approx(0.065)     # the small trace's moe/experts
    value, note = bench_run.layer_metric("moe_experts_roofline").compute(run)
    assert value == pytest.approx(100 * least / spent) and 0 < value < 100
    assert "pairs a layer" in note
