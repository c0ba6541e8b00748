"""The `glm4_moe_lite` configuration's benchmark files (ISSUE 44) on the CPU
at tiny widths, from a data root of their own (`data_glm/`): the `pretrain`
driver end to end through its data files, `correct` seen to fail under the
control and under the cell's three faults, the reference's training steps
against autodiff of the whole, the cost arithmetic, the cut's arithmetic at
the published widths against the catalog row, and every new reader on a
small trace and on runs with nothing to read."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_glm")
sys.path.insert(0, ROOT)

from chipbench import costs  # noqa: E402
from chipbench import costs_glm4_moe_lite as cg  # noqa: E402
from chipbench import program_glm4_moe_lite as program  # noqa: E402
from chipbench import reference_glm4_moe_lite as reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import scope_reduce, scope_tables  # noqa: E402
from chipbench.drivers import pretrain  # noqa: E402

CELL = "glm-4.7-flash-ep4.pretrain-16k"
TINY = "tiny-glm.pretrain"
TABLE = "components_glm4_moe_lite.json"
PEAKS = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
    "TPU v5 lite"]
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "glm-4.7-flash-ep4.json")))
S = 16384
NEW = ("mla_core_roofline", "mla_proj_ms_per_step", "mtp_ms_per_step")
# readers the benchmark had, whose lists this cell joins
OLD = ("device_idle_share.train", "train_mfu", "trace_lower_s",
       "step_host_ms", "train_step_retraces", "lower_forward_s",
       "lower_backward_s", "lower_optimizer_s", "lower_to_mlir_s",
       "lower_inner_compile_s", "attention_ms_per_step",
       "head_loss_ms_per_step", "optimizer_ms_per_step",
       "remat_recompute_ms_per_step", "moe_ms_per_step",
       "moe_experts_roofline", "moe_expert_load_max_over_mean",
       "moe_dropped_pairs", "step_hbm_peak_bytes", "step_temp_bytes",
       "kept_residual_bytes")


def _ctx(seed=7, seconds=0.5):
    return bench_run.make_ctx(DATA, TINY, seed, seconds, require_chip=False,
                              t_start=time.perf_counter())[2]


# -- the driver, end to end through the data files ---------------------------

def test_cell_end_to_end_on_the_cpu():
    out = bench_run.run_cell(DATA, TINY, 2147483693, 0.5, False,
                             require_chip=False, t_start=time.perf_counter())
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert set(out["compared"]) >= {"loss_gap.step1", "loss_gap.step2",
                                    "moe_dropped_pairs"}
    json.dumps(out)


def test_the_control_and_the_three_faults_each_fail_a_limit():
    """fp8 in the program's place, an update not applied, a doubled
    learning rate, and the module's loss left out (`mtp_loss_weight` 0 in
    the trainer's settings: the first loss is off by 0.3 L_MTP, the
    module's leaves see no gradient)."""
    out = pretrain.control(_ctx(seed=11), controls=True, faults=True)
    assert all(r["ok"] for r in out["sound"]), out["sound"]
    assert set(out) == {"sound", "fp8", "fault:update_not_applied",
                        "fault:learning_rate_doubled",
                        "fault:mtp_loss_left_out"}
    for side in set(out) - {"sound"}:
        assert not all(r["ok"] for r in out[side]), (side, out[side])
    left = {r["name"]: r for r in out["fault:mtp_loss_left_out"]}
    assert left["loss_gap.step1"]["value"] > 1.0          # 0.3 x ~5.5
    assert left["first_grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert left["first_grad_norm_gap"]["note"].startswith(("mtp.", "lm_head",
                                                           "model."))


def test_driver_finds_its_parts_and_reads_the_counters():
    assert pretrain.parts({"model_type": "glm4_moe_lite"}) == (
        program, reference, cg)
    ctx = _ctx()
    cfg = program.model_config(ctx.config)
    assert (cfg.experts_held, cfg.expert_offset, cfg.n_routed_experts) == (
        4, 4, 8)
    assert cfg.vocab_size == 256 and cfg.head_group == 5
    assert (cfg.num_nextn_predict_layers, cfg.mtp_loss_weight) == (1, 0.3)
    model, shapes = program.skeleton(cfg)
    names = [k for k, _ in model.named_parameters()]
    assert not any(k.endswith(("e_score_correction_bias", "main_loss",
                               "mtp_loss")) for k in names)
    assert {"main_loss", "mtp_loss"} <= set(shapes)
    with pytest.raises(ValueError, match="groups"):
        program.model_config(dict(ctx.config, n_group=8, topk_group=4))
    rows = pretrain.counted(ctx, {"expert_tokens": [[1, 2]],
                                  "dropped_pairs": 0, "main_loss": 1.0,
                                  "mtp_loss": 2.0})
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
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 1, 48))
    got = reference.train_steps(lambda: dict(f32), ids.astype(np.int32),
                                ctx.config, ctx.config["trainer"])
    want = reference.loss_and_grads(
        f32, jnp.asarray(ids[0], jnp.int32), ctx.config,
        reference.held_of(ctx.config), "all")
    return got, want, {
        k: float(jnp.sqrt(jnp.sum(jnp.square(v))))
        for k, v in want["total"][1].items()}


def test_reference_train_step_reads_the_losses_of_the_whole(one_step):
    got, want, grads = one_step
    assert got["losses"][0] == pytest.approx(float(want["total"][0]),
                                             rel=1e-5)
    assert got["main_losses"][0] == pytest.approx(float(want["main"][0]),
                                                  rel=1e-5)
    assert got["mtp_losses"][0] == pytest.approx(float(want["mtp"][0]),
                                                 rel=1e-5)
    assert got["losses"][0] == pytest.approx(
        got["main_losses"][0] + 0.3 * got["mtp_losses"][0], rel=1e-6)
    trained = {k for k in grads if not k.endswith(
        ("e_score_correction_bias", "expert_tokens", "dropped_pairs",
         "main_loss", "mtp_loss"))}
    assert set(got["grad_norms"]) == trained == set(got["delta_norms"])
    assert got["expert_rows"] > 0


@pytest.mark.parametrize("leaf", [
    "embed_tokens", "lm_head", "layernorm.weight", "self_attn.q_a_proj",
    "self_attn.q_b_proj", "self_attn.kv_a_proj", "self_attn.kv_b_proj",
    "self_attn.o_proj", "mlp.gate_up_proj", "mlp.down_proj", "mlp.router",
    "mlp.experts_gate_up", "mlp.experts_down", "mlp.shared_gate_up",
    "mlp.shared_down", "model.norm.weight", "mtp.enorm.weight",
    "mtp.hnorm.weight", "mtp.eh_proj", "mtp.norm.weight"])
def test_reference_train_step_is_autodiff_of_the_whole(one_step, leaf):
    """The gradient norms of a step taken half a layer at a time (inputs
    kept on the host, the module after the trunk, the head's and the
    embedding's two gradients summed before their one update) are those of
    autodiff of the whole loss."""
    got, _, grads = one_step
    names = [k for k in grads if k.endswith(leaf)]
    assert names
    for name in names:
        assert got["grad_norms"][name] == pytest.approx(
            grads[name], rel=2e-4, abs=1e-9), name


def test_precompile_compiles_the_programs_train_steps_then_runs(tmp_path,
                                                                monkeypatch):
    """From shapes alone, on its own threads: what it leaves in JAX's
    persistent cache are the nine programs `train_steps` asks for (the
    mixer, the expert half and the dense half, forward and VJP, the
    module's join forward and VJP, and the head + loss, one for both
    passes), key for key."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from chipbench import weights
    ctx = _ctx()
    cfg = program.model_config(ctx.config)
    _, shapes = program.skeleton(cfg)
    B, T = ctx.cell["batch_size"], ctx.traffic["seq_len"]
    big = ("jit__mixer_fwd", "jit__mixer_bwd", "jit__expert_fwd",
           "jit__expert_bwd", "jit__dense_fwd", "jit__dense_bwd",
           "jit__join_fwd", "jit__join_bwd", "jit__head_loss")

    def entries():
        return sorted(f for f in os.listdir(tmp_path)
                      if f.startswith(big) and not f.endswith("-atime"))

    knobs = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0,
             "jax_persistent_cache_min_entry_size_bytes": 0,
             "jax_enable_compilation_cache": True}
    was = {k: getattr(jax.config, k) for k in knobs}
    try:
        for k, v in knobs.items():
            jax.config.update(k, v)
        cc.reset_cache()
        reference._AOT.clear()
        reference.precompile(shapes, ctx.config, B, T)
        first = entries()
        assert len(first) == 9, first
        assert len(reference._AOT) == 9       # and kept for `train_steps`
        calls = []
        real = reference._mixer_fwd
        monkeypatch.setattr(reference, "_mixer_fwd", lambda *a, **k: (
            calls.append(1), real(*a, **k))[1])
        reference._mixer_fwd.__name__ = "_mixer_fwd"
        make = program.generator(shapes)
        ids = weights.token_batches(3, cfg.vocab_size, 1, B, T)
        reference.train_steps(lambda: make(3), ids[:1], ctx.config,
                              ctx.config["trainer"])
        assert entries() == first
        assert not calls          # the kept programs ran, nothing was traced
    finally:
        reference._AOT.clear()
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_generator_seeds_the_bias_small_and_the_counters_zero():
    cfg = program.model_config(_ctx().config)
    _, shapes = program.skeleton(cfg)
    make = program.generator(shapes)
    a, b, c = make(2 ** 31 + 11), make(2 ** 31 + 11), make(3)
    name = "mtp.block.mlp.e_score_correction_bias"
    bias = np.asarray(a[name])
    assert bias.shape == (8,) and bias.dtype == np.float32
    assert 0 < np.abs(bias).max() < 0.1 and np.ptp(bias) > 0.01
    for k in a:
        if k.endswith(program.ZEROS):
            assert not np.asarray(a[k]).any(), k
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    assert not np.array_equal(np.asarray(a[name]), np.asarray(c[name]))
    assert (np.asarray(a["mtp.enorm.weight"]) == 1).all()
    assert np.asarray(a["mtp.eh_proj"], np.float32).std() == pytest.approx(
        0.02, rel=0.1)


# -- the cut and the costs ----------------------------------------------------

def test_the_cut_holds_the_published_widths_and_1163_million_parameters():
    cfg = program.model_config(CONFIG)
    model, shapes = program.skeleton(cfg)

    def count(prefix):
        return sum(int(np.prod(shapes[k].shape)) for k, _ in
                   model.named_parameters() if k.startswith(prefix))

    attn = count("model.layers.0.self_attn.")
    assert attn == 21757952 + 768 + 512             # the two latent norms
    dense = count("model.layers.0.") - attn
    assert dense == 3 * 2048 * 10240 + 2 * 2048
    expert = count("model.layers.1.") - attn
    assert expert == (2048 * 64 + 17 * 3 * 2048 * 1536 + 2 * 2048)
    module = count("mtp.")
    assert module == attn + expert + 2 * 2048 * 2048 + 3 * 2048
    assert count("") == (5 * attn + dense + 4 * expert + module
                         + 2 * 38720 * 2048 + 2048) == 1163304448
    # x 8 bytes at the peak (bf16 weights, gradients, both AdamW moments)
    assert 9.3e9 < 8 * count("") < 9.31e9
    buffers = [k for k in shapes if k.endswith("e_score_correction_bias")]
    assert len(buffers) == 5 and all(shapes[k].shape == (64,)
                                     for k in buffers)
    for lyr in ("model.layers.0.self_attn.", "mtp.block.self_attn."):
        assert shapes[lyr + "q_a_proj"].shape == (2048, 768)
        assert shapes[lyr + "q_b_proj"].shape == (768, 20 * 256)
        assert shapes[lyr + "kv_a_proj"].shape == (2048, 512 + 64)
        assert shapes[lyr + "kv_b_proj"].shape == (512, 20 * (192 + 256))
        assert shapes[lyr + "o_proj"].shape == (20 * 256, 2048)
        assert lyr + "gate_proj" not in shapes
    assert shapes["model.layers.0.mlp.gate_up_proj"].shape == (2048, 20480)
    for lyr in ("model.layers.4.mlp.", "mtp.block.mlp."):
        assert shapes[lyr + "router"].shape == (2048, 64)
        assert shapes[lyr + "experts_gate_up"].shape == (16, 2048, 2 * 1536)
        assert shapes[lyr + "shared_down"].shape == (1536, 2048)
    assert shapes["mtp.eh_proj"].shape == (4096, 2048)
    assert shapes["model.embed_tokens"].shape == (38720, 2048)
    assert shapes["lm_head"].shape == (2048, 38720)
    assert [type(lyr.mlp).__name__ for lyr in model.model.layers] == [
        "Dots3NoteMLP"] + ["DroplessMoE"] * 4
    assert (cfg.head_group, cfg.moe_rows) == (
        CONFIG["program"]["head_group"], 32768)
    assert 20 % cfg.head_group == 0


def test_every_key_not_reduced_is_the_catalog_rows():
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_rows"]
    assert {k: CONFIG["reduced_from"][k] for k in CONFIG["reduced"]} == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_rows": 154880}
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_rows"]) == (5, 16, 38720)
    assert 38720 * 4 == 154880 and CONFIG["num_nextn_predict_layers"] == 1
    assert len(CONFIG["assumed"]) >= 10 and CONFIG["mtp_loss_weight"] == 0.3
    assert set(CONFIG["kernels"]) >= {
        "causal attention (splash)", "grouped matmul", "swiglu fwd",
        "swiglu bwd"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "GLM-4.7-Flash")
    assert CONFIG["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k not in CONFIG["reduced"]:
            assert CONFIG[k] == v, k
    assert CONFIG["vocab_size"] == row["config"]["vocab_size"] == 154880


def test_costs_by_hand():
    s = cg.sizes(CONFIG)
    assert (s["layers"], s["dense"], s["expert"], s["mtp"]) == (5, 1, 4, 1)
    attn = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
            + 5120 * 2048)
    assert cg.attention_params(CONFIG) == attn == 21757952
    moe = 2048 * 64 + 3 * 2048 * 1536 * (1 + 4 * 16 / 64)
    assert cg.matmul_params_per_token(CONFIG) == (
        6 * attn + 5 * moe + 3 * 2048 * 10240 + 2 * 2048 * 38720
        + 2 * 2048 * 2048)
    assert cg.causal_pairs(S) == 134225920
    flops, byts = cg.mla_core_train(CONFIG, 1, S)
    assert flops == 3 * 134225920 * 20 * 2 * (192 + 64 + 256)
    assert byts == S * 2 * (3 * 20 * 256 + 3 * (20 * 192 + 64)
                            + 6 * 20 * 256)
    assert cg.mla_core_calls(CONFIG) == 6
    assert costs.roofline_s(flops, byts, PEAKS)[1] == "compute"
    assert cg.train_flops_per_token(CONFIG, S) == pytest.approx(
        6 * cg.matmul_params_per_token(CONFIG) + 6 * flops / S)
    # the six cores are the larger half of the step's required operations
    share = 6 * flops / (S * cg.train_flops_per_token(CONFIG, S))
    assert 0.5 < share < 0.6
    flops, byts = cg.moe_experts_train(CONFIG, 16384)
    assert flops == 18 * 16384 * 2048 * 1536
    assert byts == 2 * (9 * 16 * 2048 * 1536 + 5 * 16384 * 2048)


# -- the readers ----------------------------------------------------------------

def _run(trace, steps=1, counters=None):
    run = {"kind": "train", "chips": 1, "steps_traced": steps,
           "peaks": PEAKS, "config": CONFIG, "batch_size": 1,
           "seq_len": S, "lower_s": 1.0, "counters": counters,
           "trace": None}
    if trace is not None:
        run["trace"] = {"dir": None, "scope_loaded": trace,
                        "scope_reduced": scope_reduce.reduce(trace)}
    return run


def _recorded():
    trace = json.load(open(os.path.join(DATA, "trace_glm.json")))
    return {"device": trace["device"], "spans": trace["spans"]}


def _read(name, run):
    value, note = bench_run.layer_metric(name).compute(run)
    assert isinstance(note, str) and note
    return value


@pytest.mark.parametrize("name,want_ms", [
    # the module: its named operations 2 + 7 + 4 + 9 + 11 + 2, the forward
    # loop whole (20: a kernel with no name in it among them), the backward
    # loop whole (40); the unnamed tgmm in no loop is left out
    ("mtp_ms_per_step", 2 + 7 + 4 + 9 + 11 + 2 + 20 + 40),
    ("mla_proj_ms_per_step",
     10 + 2 + 12 + 3 + 9 + 1 + 4 + 4 + 3 + 10 + 15),
    ("attention_ms_per_step",            # through components.json, unedited
     10 + 2 + 12 + 3 + 30 + 9 + 1 + 4 + 4 + 10 + 3 + 25 + 10 + 35 + 15),
    ("moe_ms_per_step", 5 + 6 + 25 + 8 + 4 + 9 + 5 + 14),
    ("head_loss_ms_per_step", 30 + 5 + 11 + 2),         # both passes
    ("optimizer_ms_per_step", 20),
    ("remat_recompute_ms_per_step", 10)])
def test_ms_readers_on_the_small_trace(name, want_ms):
    assert _read(name, _run(_recorded())) == pytest.approx(want_ms)


def test_the_modules_reader_says_what_it_counted_and_what_it_left_out():
    run = _run(_recorded(), counters={"main_loss": 10.5, "mtp_loss": 10.6})
    value, note = bench_run.layer_metric("mtp_ms_per_step").compute(run)
    assert "mtp/block=73.000" in note and "mtp/head=11.000" in note
    assert "mtp/embed=2.000" in note and "mtp/loss=2.000" in note
    assert "no loop of the module: 5.000 (not counted)" in note
    assert "main_loss=10.5 mtp_loss=10.6" in note
    # half the steps, half the time a step
    assert _read("mtp_ms_per_step", _run(_recorded(), steps=2)) == (
        pytest.approx(value / 2))


def test_core_roofline_on_the_small_trace():
    """The least time for six causal cores over the component's device
    time, trunk and module, the unnamed kernel by its name, and under
    100 %."""
    flops, byts = cg.mla_core_train(CONFIG, 1, S)
    least, bound = costs.roofline_s(6 * flops, 6 * byts, PEAKS)
    assert bound == "compute"
    got = _read("mla_core_roofline", _run(_recorded()))
    assert got == pytest.approx(100 * least / ((30 + 10 + 25 + 35) / 1e3))
    assert got > 100        # the small trace's times are not a 16k step's


def test_experts_roofline_reads_this_cell_through_its_own_table_and_costs():
    run = _run(_recorded(), counters={
        "expert_tokens": [[1024] * 16] * 5, "dropped_pairs": 0})
    assert scope_tables.table_of(run, "moe_experts",
                                 "components_solar_open2.json") == TABLE
    assert scope_tables.table_of(run, "moe",
                                 "components_solar_open2.json") == TABLE
    assert scope_tables.costs_of(run, "moe_experts_train",
                                 "costs_solar_open2") is cg
    flops, byts = cg.moe_experts_train(CONFIG, 16384)
    least, _ = costs.roofline_s(5 * flops, 5 * byts, PEAKS)
    spent = (25 + 9 + 5 + 14) / 1e3
    assert _read("moe_experts_roofline", run) == pytest.approx(
        100 * least / spent)
    assert _read("moe_dropped_pairs", run) == 0
    assert _read("moe_expert_load_max_over_mean", run) == pytest.approx(1.0)


def test_components_table_puts_the_inner_names_first():
    red, table = scope_tables.reduced(_run(_recorded()), TABLE)
    by = red["component_s"]
    assert by[("attn/core/causal", "forward")] == pytest.approx(0.040)
    assert by[("attn/core/causal", "backward")] == pytest.approx(0.060)
    assert by[("moe/experts", "forward")] == pytest.approx(0.034)
    assert by[("mtp/head", "forward")] == pytest.approx(0.011)
    assert by[("mtp/proj", "forward")] == pytest.approx(0.007)
    assert ("mtp/block", "forward") not in by or by[
        ("mtp/block", "forward")] < 0.025
    assert set(table["groups"]["mla_proj"]) == {"attn/qkv", "attn/rope",
                                                "attn/out"}
    from paddle_tpu.observability import scopes
    assert {r["scope"] for r in table["components"] if "scope" in r} <= (
        set(scopes.COMPONENTS) | set(scopes.PHASES))
    dense = json.load(open(os.path.join(ROOT, "chipbench",
                                        "components.json")))
    assert table["components"][-len(dense["components"]):] == dense[
        "components"]


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


def check_manifest(m, root=None):
    """What this cell asks of a manifest `m` whose files lie under `root`
    (the module's `ROOT` as it stands at the call, not at the definition):
    by name and by membership, so that cells after it change nothing."""
    root = root or ROOT
    tiny = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-16k")
    assert len(cell["why"]) <= 200
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert config["name"] == "glm-4.7-flash-ep4"
    assert config["reduced"] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"] and len(config["why"]) <= 200
    _, _, cell_file, config, traffic = bench_run.load_cell(root, CELL)
    assert traffic["kind"] == "pretrain" and traffic["seq_len"] == S
    assert (traffic["check_steps"], traffic["trace_steps"],
            traffic["distinct_batches"]) == (2, 2, 16)
    assert cell_file["batch_size"] == 1
    limits = cell_file["correct"]["limits"]
    assert set(limits) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap"}
    first, second = limits["loss_gap"]["limit"]      # a limit a checked step
    assert 0 < first <= second
    assert all("PLACEHOLDER" not in v["reason"] for v in limits.values())
    assert cell_file["correct"]["controls"] == ["fp8"]
    assert cell_file["correct"]["faults"] == {
        "update_not_applied": {"learning_rate": 0.0},
        "learning_rate_doubled": {
            "learning_rate": 2 * config["trainer"]["learning_rate"]},
        "mtp_loss_left_out": {"mtp_loss_weight": 0.0}}
    mine = {x["name"]: x for x in m["per_layer"]
            if CELL in x.get("workloads", [])}
    # membership only: a later cell appends itself after this one
    assert set(NEW) | set(OLD) <= set(mine)
    assert all(mine[n]["workloads"][0] == CELL for n in NEW)
    # the tiny root lists every metric the cell is listed under
    assert set(mine) <= {x["name"] for x in tiny["per_layer"]}
    assert CELL in next(x for x in m["end_to_end"]
                        if x["name"] == "train_tokens_per_s_chip")["workloads"]
    assert all(os.path.exists(os.path.join(
        ROOT, "chipbench", "layer_metrics", n + ".py")) for n in mine)
    bench_run.load_cell(DATA, TINY)


def test_manifest_names_the_cell_and_the_tiny_root_mirrors_it():
    check_manifest(json.load(open(os.path.join(ROOT, "BENCHMARK.json"))))
