"""The `lfm2_moe` configuration's benchmark files (ISSUE 51) on the CPU at
tiny widths, from a data root of their own (`data_lfm2/`): the `pretrain`
driver end to end through its data files, `correct` seen to fail under the
control and under the cell's two faults, the reference's training steps
against autodiff of the whole, the generator's seeding of the taps and the
selection bias, the cut's arithmetic at the published widths against the
catalog row, `costs_lfm2_moe` against a hand count, and the two new readers
on a small recorded trace and on runs with nothing to read."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_lfm2")
sys.path.insert(0, ROOT)

from chipbench import costs  # noqa: E402
from chipbench import costs_lfm2_moe as cl  # noqa: E402
from chipbench import program_lfm2_moe as program  # noqa: E402
from chipbench import reference_lfm2_moe as reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import scope_reduce, scope_tables  # noqa: E402
from chipbench.drivers import pretrain  # noqa: E402

CELL = "lfm2-8b-a1b-ep4.pretrain-4k-batch"
TINY = "tiny-lfm2.pretrain"
TABLE = "components_lfm2_moe.json"
PEAKS = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
    "TPU v5 lite"]
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "lfm2-8b-a1b-ep4.json")))
B, S = 4, 4096
NEW = ("conv_mixer_ms_per_step", "short_conv_roofline")
# readers the benchmark had, whose lists this cell joins
OLD = ("device_idle_share.train", "train_mfu", "trace_lower_s",
       "step_host_ms", "train_step_retraces", "lower_forward_s",
       "lower_backward_s", "lower_optimizer_s", "lower_to_mlir_s",
       "lower_inner_compile_s", "attention_ms_per_step",
       "head_loss_ms_per_step", "optimizer_ms_per_step",
       "remat_recompute_ms_per_step", "mlp_ms_per_step", "moe_ms_per_step",
       "moe_experts_roofline", "moe_expert_load_max_over_mean",
       "moe_dropped_pairs", "step_hbm_peak_bytes", "step_temp_bytes",
       "kept_residual_bytes")
FAULTS = ("update_not_applied", "learning_rate_doubled")


def _ctx(seed=7, seconds=0.5):
    return bench_run.make_ctx(DATA, TINY, seed, seconds, require_chip=False,
                              t_start=time.perf_counter())[2]


def test_driver_finds_its_parts_and_reads_the_counters():
    assert pretrain.parts({"model_type": "lfm2_moe"}) == (
        program, reference, cl)
    ctx = _ctx()
    cfg = program.model_config(ctx.config)
    assert (cfg.experts_held, cfg.expert_offset, cfg.num_experts) == (4, 4, 8)
    assert cfg.vocab_size == 256 and cfg.loss_block_rows == 32
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv")
    model, shapes = program.skeleton(cfg)
    names = [k for k, _ in model.named_parameters()]
    assert not any(k.endswith(("e_score_correction_bias", "expert_tokens"))
                   for k in names)
    assert sum(k.endswith("conv.conv_weight") for k in names) == 3
    assert not any("shared" in k or "lm_head" in k for k in shapes)
    with pytest.raises(ValueError, match="not built"):
        program.model_config(dict(ctx.config, conv_bias=True))
    with pytest.raises(ValueError, match="not built"):
        program.model_config(dict(ctx.config, use_expert_bias=False))
    rows = pretrain.counted(ctx, {"expert_tokens": [[1, 2]],
                                  "dropped_pairs": 0})
    assert [(r["name"], r["value"], r["ok"]) for r in rows] == [
        ("moe_dropped_pairs", 0, True)]


# -- the reference ------------------------------------------------------------

@pytest.fixture(scope="module")
def one_step():
    """One reference training step half a layer at a time beside autodiff
    of the whole loss, on float32 copies of seeded weights."""
    import jax.numpy as jnp
    ctx = _ctx()
    cfg = program.model_config(ctx.config)
    _, shapes = program.skeleton(cfg)
    state = program.generator(shapes)(5)
    f32 = {k: (v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v)
           for k, v in state.items()}
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 2, 48))
    got = reference.train_steps(lambda: dict(f32), ids.astype(np.int32),
                                ctx.config, ctx.config["trainer"])
    loss, grads = reference.loss_and_grads(
        f32, jnp.asarray(ids[0], jnp.int32), ctx.config,
        reference.held_of(ctx.config))
    return got, float(loss), {
        k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in grads.items()}


def test_reference_train_step_reads_the_loss_of_the_whole(one_step):
    got, loss, _ = one_step
    assert got["losses"][0] == pytest.approx(loss, rel=1e-6)
    assert 0 < got["expert_rows"] <= 48


@pytest.mark.parametrize("leaf", [
    "model.embed_tokens", "model.norm.weight",
    "model.layers.0.conv.in_proj", "model.layers.0.mlp.gate_up_proj",
    "model.layers.1.conv.conv_weight", "model.layers.1.mlp.router",
    "model.layers.2.self_attn.qkv_proj",
    "model.layers.2.self_attn.q_layernorm.weight",
    "model.layers.2.self_attn.k_layernorm.weight",
    "model.layers.3.conv.out_proj", "model.layers.3.mlp.experts_down"])
def test_reference_train_step_is_autodiff_of_the_whole(one_step, leaf):
    """The half-layer-at-a-time backward, the tied table's two uses summed:
    a leaf's first gradient norm is jax.grad's of the whole loss."""
    got, _, norms = one_step
    assert got["grad_norms"][leaf] == pytest.approx(norms[leaf], rel=2e-4)
    assert norms[leaf] > 0


def test_generator_seeds_the_taps_the_bias_and_the_counters():
    cfg = program.model_config(_ctx().config)
    _, shapes = program.skeleton(cfg)
    make = program.generator(shapes)
    a, b = make(2 ** 31 + 11), make(2 ** 31 + 11)
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    for k in a:
        if k.endswith(program.ZEROS):
            assert not np.asarray(a[k]).any(), k
    taps = np.asarray(a["model.layers.1.conv.conv_weight"], np.float32)
    assert taps.shape == (3, 64)
    assert 0.4 < np.abs(taps).max() <= 3 ** -0.5 + 1e-3
    assert taps.std() == pytest.approx(1 / 3, rel=0.15)
    assert (np.asarray(a["model.layers.2.self_attn.q_layernorm.weight"])
            == 1).all()
    bias = np.asarray(a["model.layers.1.mlp.e_score_correction_bias"])
    assert bias.shape == (8,) and 0 < np.abs(bias).max() < 0.1
    # each chip's four experts (held here, or absent) draw no more than
    # their share: the bias sums to zero over them, and is not all alike
    assert np.abs(bias.reshape(2, 4).sum(axis=1)).max() < 1e-6
    assert bias.reshape(2, 4).std(axis=1).min() > 1e-3
    proj = np.asarray(a["model.layers.1.conv.in_proj"], np.float32)
    assert proj.std() == pytest.approx(0.02, rel=0.1)


# -- the cut and the costs ----------------------------------------------------

def test_every_key_not_reduced_is_the_catalog_rows():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_rows"]
    assert {k: CONFIG["reduced_from"][k] for k in CONFIG["reduced"]} == {
        "num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32,
        "vocab_rows": 65536}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["vocab_rows"]) == (9, 1, 8, 16384)
    assert 16384 * 4 == 65536 and CONFIG["expert_offset"] == 0
    assert len(CONFIG["layer_types"]) == 24          # kept whole
    assert CONFIG["layer_types"][:9] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv"]
    for k in ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads", "conv_L_cache"):
        assert isinstance(CONFIG[k], int) and CONFIG[k] > 0
        assert k not in CONFIG["reduced"]
    assert len(CONFIG["assumed"]) >= 8
    assert sum("ASSUMED" in a for a in CONFIG["assumed"]) >= 4
    assert "EP4" in CONFIG["deployment"]
    assert "921,256,448" in CONFIG["deployment"]
    assert CONFIG["moe_rows"] == 32768 and CONFIG["chips"] == 1
    assert CONFIG["trainer"]["learning_rate"] == 3e-05
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "LFM2-8B-A1B")
    assert CONFIG["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k not in CONFIG["reduced"]:
            assert CONFIG[k] == v, k
    assert CONFIG["model_type"] == "lfm2_moe"


def test_costs_by_hand():
    s = cl.sizes(CONFIG)
    assert (s["layers"], s["conv"], s["attention"], s["dense"],
            s["expert"]) == (9, 7, 2, 1, 8)
    assert (s["held"], s["total"], s["k"], s["vocab"], s["d"]) == (
        8, 32, 4, 16384, 64)
    h = 2048
    gqa = h * (32 + 16) * 64 + 32 * 64 * h
    moe = h * 32 + 3 * h * 1792 * 4 * 8 / 32
    assert cl.matmul_params_per_token(CONFIG) == (
        7 * 4 * h * h + 2 * gqa + 8 * moe + 3 * h * 7168 + h * 16384)
    per_token = cl.train_flops_per_token(CONFIG, S)
    assert per_token == pytest.approx(
        6 * cl.matmul_params_per_token(CONFIG) + 6 * 2 * 32 * 64 * S)
    assert per_token == pytest.approx(1.928e9, rel=1e-3)
    # the shares the cell's `why` gives: conv mixers, experts, dense, head,
    # the two attention cores
    assert 6 * 7 * 4 * h * h / per_token == pytest.approx(0.365, abs=0.005)
    assert 6 * 8 * moe / per_token == pytest.approx(0.276, abs=0.005)
    assert 6 * 3 * h * 7168 / per_token == pytest.approx(0.137, abs=0.005)
    assert 6 * h * 16384 / per_token == pytest.approx(0.104, abs=0.005)
    assert 6 * 2 * 32 * 64 * S / per_token == pytest.approx(0.052, abs=0.005)
    flops, byts = cl.short_conv_train(CONFIG, B, S)
    T = B * S
    # forward bcx read (3) + y written (1); backward bcx (3) + dy (1) read
    # and dbcx (3) written: 11 elements a channel a token, bf16
    assert byts == T * 2048 * 11 * 2 == 738_197_504
    assert flops == T * 2048 * (8 * 3 + 6)
    assert costs.roofline_s(flops, byts, PEAKS)[1] == "memory"
    # at the roofline: 7 layers of a ~0.6 s step
    least = 7 * costs.roofline_s(flops, byts, PEAKS)[0]
    assert 0.005 < least < 0.008
    assert cl.moe_experts_train(CONFIG, 16384) == (
        18 * 16384 * h * 1792, (3 * 8 * 3 * h * 1792 + 5 * 16384 * h) * 2)


# -- the readers ----------------------------------------------------------------

def _run(trace, steps=1, counters=None, config=CONFIG):
    run = {"kind": "train", "chips": 1, "steps_traced": steps,
           "peaks": PEAKS, "config": config, "batch_size": B,
           "seq_len": S, "lower_s": 1.0, "counters": counters,
           "trace": None}
    if trace is not None:
        run["trace"] = {"dir": None, "scope_loaded": trace,
                        "scope_reduced": scope_reduce.reduce(trace)}
    return run


def _recorded():
    trace = json.load(open(os.path.join(DATA, "trace_lfm2.json")))
    return {"device": trace["device"], "spans": trace["spans"]}


def _read(name, run):
    value, note = bench_run.layer_metric(name).compute(run)
    assert isinstance(note, str) and note
    return value


# conv/proj 8 forward, 8 recomputed, 16 backward; conv/core 3 + 3 (the
# forward kernel twice) + 6 (the backward kernel), by their names; conv/out
# 4 forward, 8 backward
PROJ, CORE, OUT = 8 + 8 + 16, 3 + 3 + 6, 4 + 8


def test_conv_mixer_ms_reader_on_the_small_trace():
    value, note = bench_run.layer_metric("conv_mixer_ms_per_step").compute(
        _run(_recorded()))
    assert value == pytest.approx(PROJ + CORE + OUT)
    assert "recomputed=8.000" in note and "conv/proj=32.000" in note
    assert "conv/core=12.000" in note and "conv/out=12.000" in note
    assert _read("conv_mixer_ms_per_step", _run(_recorded(), steps=2)) == (
        pytest.approx(value / 2))


def test_short_conv_roofline_on_the_small_trace():
    flops, byts = cl.short_conv_train(CONFIG, B, S)
    least, bound = costs.roofline_s(7 * flops, 7 * byts, PEAKS)
    assert bound == "memory"
    value, note = bench_run.layer_metric("short_conv_roofline").compute(
        _run(_recorded()))
    assert value == pytest.approx(100 * least / (CORE / 1e3))
    assert value < 100
    assert "bound=memory" in note and "7 conv layers" in note


def test_the_shared_readers_read_this_cell_through_its_own_files():
    run = _run(_recorded(), counters={
        "expert_tokens": [[2048] * 8] * 8, "dropped_pairs": 0})
    for group in ("moe_experts", "moe", "conv", "short_conv"):
        assert scope_tables.table_of(
            run, group, "components_solar_open2.json") == TABLE
    assert scope_tables.costs_of(run, "moe_experts_train",
                                 "costs_solar_open2") is cl
    assert scope_tables.costs_of(run, "short_conv_train") is cl
    assert scope_tables.costs_of(run, "ssm_conv_train") is None
    flops, byts = cl.moe_experts_train(CONFIG, 16384)
    least, _ = costs.roofline_s(8 * flops, 8 * byts, PEAKS)
    assert _read("moe_experts_roofline", run) == pytest.approx(
        100 * least / ((20 + 20 + 35) / 1e3))
    assert _read("moe_ms_per_step", run) == pytest.approx(
        1 + 2 + 4 + 20 + 20 + 35 + 3)
    # components.json's own groups: the dense layer's kernels by name (no
    # shared expert shares them), attention without the q/k norm's scope
    assert _read("mlp_ms_per_step", run) == pytest.approx(12 + 6 + 14 + 9)
    assert _read("attention_ms_per_step", run) == pytest.approx(
        5 + 10 + 1 + 3 + 7 + 15 + 3)
    assert _read("head_loss_ms_per_step", run) == pytest.approx(30)
    assert _read("remat_recompute_ms_per_step", run) == pytest.approx(20 + 8)
    assert bench_run.layer_metric("moe_dropped_pairs").compute(run)[0] == 0
    # another architecture's readers find nothing here
    for other in ("ssm_conv_roofline", "hc_ms_per_step", "hc_mix_roofline"):
        assert bench_run.layer_metric(other).compute(run) is None


def test_components_table_puts_the_models_names_first():
    red, table = scope_tables.reduced(_run(_recorded()), TABLE)
    by = red["component_s"]
    assert by[("conv/proj", "recomputed")] == pytest.approx(0.008)
    assert by[("conv/core", "forward")] == pytest.approx(0.006)
    assert by[("conv/core", "backward")] == pytest.approx(0.006)
    assert by[("attn/qk_norm", "forward")] == pytest.approx(0.002)
    assert by[("attn/qk_norm", "backward")] == pytest.approx(0.002)
    assert by[("mlp", "forward")] == pytest.approx(0.018)
    assert ("moe/shared", "forward") not in by
    from paddle_tpu.observability import scopes
    assert {r["scope"] for r in table["components"] if "scope" in r} <= (
        set(scopes.COMPONENTS) | set(scopes.PHASES))
    for name in ("conv/proj", "conv/core", "conv/out", "attn/qk_norm"):
        assert name in scopes.COMPONENTS
    base = scope_reduce.rules()
    assert table["components"][-len(base["components"]):] == (
        base["components"])
    assert table["groups"]["conv"] == ["conv/proj", "conv/core", "conv/out"]
    assert "moe/shared" not in table["groups"]["moe"]
    assert scope_tables.ms_per_step(_run(_recorded()), TABLE, "qk_norm")[
        0] == pytest.approx(4)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_with_nothing_to_read(name):
    """No trace, a trace without names (a program that names nothing),
    another architecture's run: None, and nothing raises."""
    old = json.load(open(os.path.join(HERE, "data", "trace_small.json")))
    unnamed = {"device": {p: [[n, s, d, None] for n, s, d in ev]
                          for p, ev in old["device"].items()},
               "spans": old["spans"]}
    for run in (_run(None), _run(unnamed)):
        assert bench_run.layer_metric(name).compute(run) is None
    other = _run(_recorded())
    other["config"] = {"model_type": "llama"}
    assert bench_run.layer_metric(name).compute(other) is None
    # the parent's program under this PR's benchmark files: the cell's
    # configuration, a trace in which nothing is under conv/*
    parent = {"device": {p: [[n, s, d, op and op.replace("conv/", "cnv/")]
                             for n, s, d, op in ev if "gate_conv" not in n]
                         for p, ev in _recorded()["device"].items()},
              "spans": _recorded()["spans"]}
    assert bench_run.layer_metric(name).compute(_run(parent)) is None


def check_manifest(m, root=None):
    """What this cell asks of a manifest `m` whose files lie under `root`:
    by name and by membership, so that cells after it change nothing."""
    root = root or ROOT
    tiny = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-4k-batch")
    assert len(cell["why"]) <= 200
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert config["name"] == "lfm2-8b-a1b-ep4"
    assert config["reduced"] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"] and len(config["why"]) <= 200
    _, _, cell_file, config, traffic = bench_run.load_cell(root, CELL)
    assert pretrain.parts(config) == (program, reference, cl)
    assert traffic["kind"] == "pretrain" and traffic["seq_len"] == S
    assert (traffic["check_steps"], traffic["trace_steps"],
            traffic["distinct_batches"]) == (2, 4, 16)
    assert cell_file["batch_size"] == B
    limits = cell_file["correct"]["limits"]
    assert set(limits) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap"}
    # a limit a checked step: the list's length is check_steps
    assert len(limits["loss_gap"]["limit"]) == traffic["check_steps"]
    assert all(0 < x < 0.1 for x in limits["loss_gap"]["limit"])
    assert all("PROVISIONAL" not in v["reason"] for v in limits.values())
    assert all("my chip run" in v["reason"] for v in limits.values())
    assert cell_file["correct"]["controls"] == ["fp8"]
    assert cell_file["correct"]["faults"] == {
        "update_not_applied": {"learning_rate": 0.0},
        "learning_rate_doubled": {
            "learning_rate": 2 * config["trainer"]["learning_rate"]}}
    mine = {x["name"]: x for x in m["per_layer"]
            if CELL in x.get("workloads", [])}
    assert set(NEW) | set(OLD) <= set(mine)
    assert all(mine[n]["workloads"][0] == CELL for n in NEW)
    assert all(mine[n]["layer"] == "kernels" for n in NEW)
    assert mine["short_conv_roofline"]["unit"] == "%"
    # readers whose count of the work reads another shape than this
    # cell's (nine dense layers, the whole vocabulary): left out
    assert not {"mlp_roofline", "head_loss_roofline"} & set(mine)
    assert set(mine) <= {x["name"] for x in tiny["per_layer"]}
    assert CELL in next(x for x in m["end_to_end"]
                        if x["name"] == "train_tokens_per_s_chip")["workloads"]
    assert all(os.path.exists(os.path.join(
        ROOT, "chipbench", "layer_metrics", n + ".py")) for n in mine)
    bench_run.load_cell(DATA, TINY)


def test_manifest_names_the_cell_and_the_tiny_root_mirrors_it():
    check_manifest(json.load(open(os.path.join(ROOT, "BENCHMARK.json"))))


def test_a_root_with_one_more_cell_after_this_one_still_passes(tmp_path):
    """`one_more_cell.py` makes a root with one more cell after this one:
    the manifest check holds on it, and this cell is still found."""
    sys.path.insert(0, HERE)
    import one_more_cell
    root = one_more_cell.copy_of(ROOT, tmp_path)
    name, new = one_more_cell.append_cell(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [w["name"] for w in m["workloads"]]
    assert names[-1] == new and CELL in names[:-1]
    check_manifest(m, root)
    bench_run.load_cell(root, new)


# -- the cell at tiny widths through the driver --------------------------------

def test_cell_end_to_end_on_the_cpu():
    out = bench_run.run_cell(DATA, TINY, 2147483693, 0.5, False,
                             require_chip=False, t_start=time.perf_counter())
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["compared"]) >= {"loss_gap.step1", "loss_gap.step2",
                                    "first_grad_norm_gap",
                                    "param_change_norm_gap",
                                    "moe_dropped_pairs"}
    json.dumps(out)


def test_the_control_and_the_two_faults_each_fail_a_limit():
    """fp8 in the program's place, an update not applied, a doubled
    learning rate."""
    out = pretrain.control(_ctx(seed=11), controls=True, faults=True)
    assert all(r["ok"] for r in out["sound"]), out["sound"]
    assert set(out) == {"sound", "fp8"} | {"fault:" + f for f in FAULTS}
    for side in set(out) - {"sound"}:
        assert not all(r["ok"] for r in out[side]), (side, out[side])
    still = {r["name"]: r for r in out["fault:update_not_applied"]}
    assert still["param_change_norm_gap"]["value"] == pytest.approx(1.0)
