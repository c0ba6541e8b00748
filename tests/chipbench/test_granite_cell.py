"""The `granitemoehybrid` configuration's benchmark files (PR 31) on the
CPU at tiny widths, from a data root of their own (`data_granite/`): the
`pretrain` driver end to end through its data files, `correct` seen to
fail under the control, the reference's training steps against autodiff
of the whole, the cost arithmetic, the cut's arithmetic at the published
widths, and every new reader on a small trace and on runs with nothing
to read."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_granite")
sys.path.insert(0, ROOT)

from chipbench import costs, costs_components  # noqa: E402
from chipbench import costs_granitemoehybrid as cg  # noqa: E402
from chipbench import program_granitemoehybrid as program  # noqa: E402
from chipbench import reference_granitemoehybrid as reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import scope_reduce, scope_tables  # noqa: E402
from chipbench.drivers import pretrain  # noqa: E402

CELL = "granite-4.0-h-micro-pp4.pretrain-32k"
TINY = "tiny-granite.pretrain"
TABLE = "components_granitemoehybrid.json"
PEAKS = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
    "TPU v5 lite"]
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "granite-4.0-h-micro-pp4.json")))
NEW = ("ssm_ms_per_step", "ssd_core_roofline", "ssm_conv_roofline")
# readers the benchmark had, which read this cell through components.json
OLD = ("attention_ms_per_step", "mlp_ms_per_step", "mlp_roofline",
       "head_loss_ms_per_step", "head_loss_roofline",
       "optimizer_ms_per_step", "remat_recompute_ms_per_step")


def _ctx(seed=7, seconds=0.5):
    return bench_run.make_ctx(DATA, TINY, seed, seconds, require_chip=False,
                              t_start=time.perf_counter())[2]


# -- the driver, end to end through the data files ---------------------------

def test_cell_end_to_end_on_the_cpu():
    out = bench_run.run_cell(DATA, TINY, 2147483659, 0.5, False,
                             require_chip=False, t_start=time.perf_counter())
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    json.dumps(out)


def test_control_in_fp8_fails_a_limit_and_the_sound_run_none():
    out = pretrain.control(_ctx(seed=11))
    assert all(r["ok"] for r in out["sound"]), out["sound"]
    assert not all(r["ok"] for r in out["fp8"]), out["fp8"]


def test_driver_finds_its_parts_and_a_model_without_experts_answers():
    """`drivers/pretrain.py` asks every program for the expert layer's
    counters: no pair dropped, no expert, no rows."""
    assert pretrain.parts({"model_type": "granitemoehybrid"}) == (
        program, reference, cg)
    counters = program.counters(None)
    assert counters == {"expert_tokens": [], "dropped_pairs": 0}
    rows = pretrain.counted(_ctx(), counters)
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
    f32 = {k: v.astype(jnp.float32) for k, v in state.items()}
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 1, 40))
    got = reference.train_steps(lambda: dict(f32), ids.astype(np.int32),
                                ctx.config, ctx.config["trainer"])
    want_loss, grads = reference.loss_and_grads(
        f32, jnp.asarray(ids[0], jnp.int32), ctx.config)
    return got, float(want_loss), {
        k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in grads.items()}


def test_reference_train_step_reads_the_loss_of_the_whole(one_step):
    got, want_loss, grads = one_step
    assert got["losses"][0] == pytest.approx(want_loss, rel=1e-5)
    assert set(got["grad_norms"]) == set(grads) == set(got["delta_norms"])
    assert got["expert_rows"] == []


@pytest.mark.parametrize("leaf", [
    "embed_tokens", "layernorm.weight", "mamba.in_proj", "mamba.conv_weight",
    "mamba.conv_bias", "mamba.A_log", "mamba.dt_bias", "mamba.D",
    "mamba.norm.weight", "mamba.out_proj", "self_attn.qkv_proj",
    "self_attn.o_proj", "shared_mlp.gate_up_proj", "shared_mlp.down_proj",
    "model.norm.weight"])
def test_reference_train_step_is_autodiff_of_the_whole(one_step, leaf):
    """The gradient norms of a step taken half a layer at a time (inputs
    kept on the host, the table's two uses added up) are those of
    autodiff of the whole loss."""
    got, _, grads = one_step
    names = [k for k in grads if k.endswith(leaf)]
    assert names
    for name in names:
        assert got["grad_norms"][name] == pytest.approx(
            grads[name], rel=2e-4, abs=1e-9), name


def test_precompile_compiles_the_programs_train_steps_then_runs(tmp_path):
    """From shapes alone, on its own threads: what it leaves in JAX's
    persistent cache are the seven programs `train_steps` asks for (two
    kinds of mixer and the MLP half, forward and VJP, and the head +
    loss), key for key, so a checkout's first run finds them compiled."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from chipbench import weights
    ctx = _ctx()
    cfg = program.model_config(ctx.config)
    _, shapes = program.skeleton(cfg)
    B, S = ctx.cell["batch_size"], ctx.traffic["seq_len"]
    big = ("jit__mixer_fwd", "jit__mixer_bwd", "jit__mlp_fwd",
           "jit__mlp_bwd", "jit__head_loss")

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
        reference.precompile(shapes, ctx.config, B, S)
        first = entries()
        assert len(first) == 7, first
        make = program.generator(shapes)
        ids = weights.token_batches(3, cfg.vocab_size, 1, B, S)
        reference.train_steps(lambda: make(3), ids[:1], ctx.config,
                              ctx.config["trainer"])
        assert entries() == first
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_generator_initialises_the_state_space_leaves_as_the_family_does():
    cfg = program.model_config(_ctx().config)
    _, shapes = program.skeleton(cfg)
    make = program.generator(shapes)
    a, b, c = make(2 ** 31 + 11), make(2 ** 31 + 11), make(3)
    lyr = "model.layers.1.mamba."
    rate = np.exp(np.asarray(a[lyr + "A_log"]))
    assert rate.min() >= 1 and rate.max() <= 16 and np.ptp(rate) > 1
    step = np.log1p(np.exp(np.asarray(a[lyr + "dt_bias"], np.float64)))
    assert step.min() >= 0.99e-3 and step.max() <= 1.01e-1
    taps = np.asarray(a[lyr + "conv_weight"], np.float32)
    assert 0.2 < np.abs(taps).mean() < 0.3 and np.abs(taps).max() <= 0.5
    bias = np.asarray(a[lyr + "conv_bias"], np.float32)
    assert 0 < np.abs(bias).max() < 0.1
    assert (np.asarray(a[lyr + "D"]) == 1).all()
    assert a[lyr + "A_log"].dtype == a[lyr + "D"].dtype == np.float32
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    assert not np.array_equal(np.asarray(a["model.embed_tokens"]),
                              np.asarray(c["model.embed_tokens"]))


# -- the cut and the costs ----------------------------------------------------

def test_the_cut_holds_the_published_widths_and_952_million_parameters():
    cfg = program.model_config(CONFIG)
    model, shapes = program.skeleton(cfg)
    n = sum(int(np.prod(shapes[k].shape)) for k, _ in
            model.named_parameters())
    assert n == 951991232                        # x 8 bytes = 7.62 GB
    mamba = sum(int(np.prod(s.shape)) for k, s in shapes.items()
                if k.startswith("model.layers.0."))
    attn = sum(int(np.prod(s.shape)) for k, s in shapes.items()
               if k.startswith("model.layers.5."))
    assert (mamba, attn) == (76182976, 60821504)
    assert n == 9 * mamba + attn + 100352 * 2048 + 2048
    lyr = "model.layers.0.mamba."
    assert shapes[lyr + "in_proj"].shape == (2048, 2 * 4096 + 2 * 128 + 64)
    assert shapes[lyr + "conv_weight"].shape == (4, 4096 + 2 * 128)
    assert shapes[lyr + "conv_bias"].shape == (4352,)
    assert shapes[lyr + "out_proj"].shape == (4096, 2048)
    assert shapes[lyr + "norm.weight"].shape == (4096,)
    assert shapes["model.layers.5.self_attn.qkv_proj"].shape == (
        2048, (32 + 16) * 64)
    assert shapes["model.layers.0.shared_mlp.gate_up_proj"].shape == (
        2048, 2 * 8192)
    assert shapes["model.embed_tokens"].shape == (100352, 2048)
    assert not any("lm_head" in k for k in shapes)
    assert [hasattr(lyr, "self_attn") for lyr in model.model.layers] == [
        i == 5 for i in range(10)]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["reduced_from"] == {"num_hidden_layers": 40}
    assert set(CONFIG["kernels"]) >= {"ssd chunk scan", "ssd chunk scan bwd",
                                      "ssm conv fwd", "ssm conv bwd"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "granite-4.0-h-micro")
        assert CONFIG["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in CONFIG["reduced"]:
                assert CONFIG[k] == v, k
        assert row["config"]["num_hidden_layers"] == 40


def test_costs_by_hand():
    s = cg.sizes(CONFIG)
    assert (s["mamba"], s["attention"], s["layers"]) == (9, 1, 10)
    mamba = 2048 * 8512 + 4096 * 2048
    gqa = 2048 * 3072 + 2048 * 2048
    per_token = 9 * mamba + gqa + 10 * 3 * 2048 * 8192 + 2048 * 100352
    assert cg.matmul_params_per_token(CONFIG) == per_token
    core = 256 * 128 + 64 * (256 * 64 + 4 * 128 * 64)
    assert cg.ssd_core_per_token(CONFIG) == core == 3178496
    assert cg.train_flops_per_token(CONFIG, 32768) == (
        6 * per_token + 6 * 32 * 64 * 32768 + 27 * core)
    flops, byts = cg.ssd_core_train(CONFIG, 1, 32768)
    assert flops == 3 * 32768 * core
    assert byts == 32768 * (5 * 4096 * 2 + 6 * 128 * 2 + 3 * 64 * 4)
    flops, byts = cg.ssm_conv_train(CONFIG, 1, 32768)
    assert flops == 32768 * 4352 * 39 and byts == 32768 * 4352 * 10
    # the shared readers' counts read this configuration's keys
    flops, _ = costs_components.mlp_train(CONFIG, 1, 32768)
    assert flops == 18 * 32768 * 2048 * 8192 + 14 * 32768 * 8192
    flops, _ = costs_components.head_loss_train(CONFIG, 1, 32768)
    assert flops == 6 * 32768 * 2048 * 100352 + 8 * 32768 * 100352


# -- the readers ----------------------------------------------------------------

def _run(trace, steps=1):
    run = {"kind": "train", "chips": 1, "steps_traced": steps,
           "peaks": PEAKS, "config": CONFIG, "batch_size": 1,
           "seq_len": 32768, "lower_s": 1.0, "counters": None,
           "trace": None}
    if trace is not None:
        run["trace"] = {"dir": None, "scope_loaded": trace,
                        "scope_reduced": scope_reduce.reduce(trace)}
    return run


def _recorded():
    trace = json.load(open(os.path.join(DATA, "trace_granite.json")))
    return {"device": trace["device"], "spans": trace["spans"]}


def _read(name, run):
    value, note = bench_run.layer_metric(name).compute(run)
    assert isinstance(note, str) and note
    return value


@pytest.mark.parametrize("name,want_ms", [
    ("ssm_ms_per_step", 40 + 5 + 2 + 10 + 12 + 21 + 10 + 30 + 10 + 5 + 5),
    ("mlp_ms_per_step", 22 + 28), ("attention_ms_per_step", 30 + 10),
    ("head_loss_ms_per_step", 35 + 5), ("optimizer_ms_per_step", 20),
    ("remat_recompute_ms_per_step", 10)])
def test_ms_readers_on_the_small_trace(name, want_ms):
    assert _read(name, _run(_recorded())) == pytest.approx(want_ms)


@pytest.mark.parametrize("name,cost,spent_s", [
    ("ssd_core_roofline", cg.ssd_core_train, 0.055),
    ("ssm_conv_roofline", cg.ssm_conv_train, 0.020)])
def test_roofline_readers_on_the_small_trace(name, cost, spent_s):
    """The least time for the nine layers' required work over the
    component's device time, recomputation in the time: memory-bound by
    both counts, and under 100 %."""
    flops, byts = cost(CONFIG, 1, 32768)
    least, bound = costs.roofline_s(9 * flops, 9 * byts, PEAKS)
    assert bound == "memory"
    got = _read(name, _run(_recorded()))
    assert got == pytest.approx(100 * least / spent_s)
    assert 0 < got < 100


def test_shared_rooflines_read_this_configuration():
    run = _run(_recorded())
    flops, byts = costs_components.mlp_train(CONFIG, 1, 32768)
    least = costs.roofline_s(10 * flops, 10 * byts, PEAKS)[0]
    assert _read("mlp_roofline", run) == pytest.approx(100 * least / 0.050)
    flops, byts = costs_components.head_loss_train(CONFIG, 1, 32768)
    least = costs.roofline_s(flops, byts, PEAKS)[0]
    assert _read("head_loss_roofline", run) == pytest.approx(
        100 * least / 0.040)


def test_components_table_splits_the_state_space_layer():
    red, table = scope_tables.reduced(_run(_recorded()), TABLE)
    by = red["component_s"]
    assert by[("ssm/core", "recomputed")] == pytest.approx(0.010)
    assert by[("ssm/core", "backward")] == pytest.approx(0.035)
    assert by[("ssm/conv", "forward")] == pytest.approx(0.010)
    assert by[("ssm/proj", "forward")] == pytest.approx(0.040)
    assert set(table["groups"]["ssm"]) == {
        "ssm/proj", "ssm/conv", "ssm/dt", "ssm/core", "ssm/norm", "ssm/out"}
    from paddle_tpu.observability import scopes
    assert {r["scope"] for r in table["components"] if "scope" in r} <= (
        set(scopes.COMPONENTS) | set(scopes.PHASES))


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
    dense = _run(_recorded())
    dense["config"] = {"model_type": "llama"}
    if name != "ssm_ms_per_step":
        assert bench_run.layer_metric(name).compute(dense) is None


def check_manifest(m, root=None):
    """What this cell asks of a manifest `m` whose files lie under `root`
    (the module's `ROOT` as it stands at the call, not at the definition):
    by name and by membership, so that cells after it change nothing."""
    root = root or ROOT
    tiny = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-32k")
    assert len(cell["why"]) <= 200
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers"]
    assert len(config["why"]) <= 200
    _, _, cell_file, config, traffic = bench_run.load_cell(root, CELL)
    assert traffic["kind"] == "pretrain" and traffic["seq_len"] == 32768
    assert cell_file["batch_size"] == 1
    assert set(cell_file["correct"]["limits"]) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap"}
    assert cell_file["correct"]["controls"] == ["fp8"]
    mine = {x["name"]: x for x in m["per_layer"]
            if CELL in x.get("workloads", [])}
    assert set(NEW) | set(OLD) <= set(mine)
    # the tiny root lists every metric the cell is listed under
    assert set(mine) <= {x["name"] for x in tiny["per_layer"]}
    assert CELL in next(x for x in m["end_to_end"]
                        if x["name"] == "train_tokens_per_s_chip")["workloads"]
    bench_run.load_cell(DATA, TINY)


def test_manifest_names_the_cell_and_the_tiny_root_mirrors_it():
    check_manifest(json.load(open(os.path.join(ROOT, "BENCHMARK.json"))))
