"""The `solar_open2` configuration's benchmark files (PR 27) on the CPU
at tiny widths, from a data root of their own (`data_solar/`): the
`pretrain` driver end to end through its data files, `correct` seen to
fail under the control, the two copies of the reference, the cost
arithmetic, the cut's arithmetic at the published widths, and every new
reader on a hand-written trace and on runs with nothing to read."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_solar")
sys.path.insert(0, ROOT)

from chipbench import costs, costs_solar_open2, program_solar_open2  # noqa: E402
from chipbench import reference_solar_open2 as reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import scope_reduce, scope_tables  # noqa: E402
from chipbench.drivers import pretrain  # noqa: E402

CELL = "solar-open2-250b-ep40.pretrain-32k"
TINY = "tiny-solar.pretrain"
TABLE = "components_solar_open2.json"
PEAKS = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
    "TPU v5 lite"]
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "solar-open2-250b-ep40.json")))
NEW = ("linear_attn_ms_per_step", "kda_core_roofline", "moe_ms_per_step",
       "moe_experts_roofline", "moe_expert_load_max_over_mean",
       "moe_dropped_pairs")


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


def test_dropped_pairs_make_a_run_incorrect():
    rows = pretrain.counted(_ctx(), {"dropped_pairs": 3,
                                     "expert_tokens": [[1, 2]]})
    assert [r["ok"] for r in rows] == [False]
    assert rows[0]["name"] == "moe_dropped_pairs" and rows[0]["limit"] == 0


def test_driver_finds_its_parts_by_model_type():
    program, ref, cost = pretrain.parts({"model_type": "solar_open2"})
    assert (program, ref, cost) == (program_solar_open2, reference,
                                    costs_solar_open2)
    with pytest.raises(ImportError):
        pretrain.parts({"model_type": "no_such_architecture"})


# -- the reference, twice -----------------------------------------------------

def _below_docstring(path):
    text = open(path).read()
    return text[text.index('"""\nfrom __future__') + 4:]


def test_the_two_copies_of_the_reference_are_one_text():
    plain = _below_docstring(os.path.join(ROOT, "tests", "reference",
                                          "solar_open2.py"))
    bench = _below_docstring(os.path.join(ROOT, "chipbench",
                                          "reference_solar_open2.py"))
    own = bench.index("# -- the benchmark's own: training steps")
    assert bench[:own].rstrip() == plain.rstrip()
    assert "paddle_tpu" not in plain and "paddle_tpu" not in bench[own:]


def test_reference_train_step_is_autodiff_of_the_whole():
    """One step a layer at a time (forward kept on the host, VJP a
    layer) gives the gradient norms autodiff of the whole loss gives."""
    import jax.numpy as jnp
    ctx = _ctx()
    cfg = program_solar_open2.model_config(ctx.config)
    _, shapes = program_solar_open2.skeleton(cfg)
    state = program_solar_open2.generator(shapes)(5)
    f32 = {k: v.astype(jnp.float32) for k, v in state.items()}
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 1, 40))
    got = reference.train_steps(lambda: dict(f32), ids.astype(np.int32),
                                ctx.config, ctx.config["trainer"])
    want_loss, grads = reference.loss_and_grads(
        f32, jnp.asarray(ids[0], jnp.int32), ctx.config,
        reference.held_of(ctx.config))
    assert got["losses"][0] == pytest.approx(float(want_loss), rel=1e-5)
    assert set(got["grad_norms"]) == {
        k for k in state if not k.endswith(("expert_tokens",
                                            "dropped_pairs"))}
    for name, norm in got["grad_norms"].items():
        want = float(jnp.sqrt(jnp.sum(jnp.square(grads[name]))))
        assert norm == pytest.approx(want, rel=2e-4, abs=1e-9), name
    assert got["expert_rows"] <= 40


def test_precompile_compiles_the_programs_train_steps_then_runs(tmp_path):
    """From shapes alone, on its own threads: what it leaves in JAX's
    persistent cache are the seven programs `train_steps` asks for (two
    kinds of mixer and the expert half, forward and VJP, and the head +
    loss), key for key, so a checkout's first run finds them compiled."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from chipbench import weights
    ctx = _ctx()
    cfg = program_solar_open2.model_config(ctx.config)
    _, shapes = program_solar_open2.skeleton(cfg)
    B, S = ctx.cell["batch_size"], ctx.traffic["seq_len"]
    big = ("jit__mixer_fwd", "jit__mixer_bwd", "jit__expert_fwd",
           "jit__expert_bwd", "jit__head_loss")

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
        make = program_solar_open2.generator(shapes)
        ids = weights.token_batches(3, cfg.vocab_size, 1, B, S)
        reference.train_steps(lambda: make(3), ids[:1], ctx.config,
                              ctx.config["trainer"])
        assert entries() == first
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_generator_gives_the_decay_its_range_and_the_counters_zero():
    cfg = program_solar_open2.model_config(_ctx().config)
    _, shapes = program_solar_open2.skeleton(cfg)
    make = program_solar_open2.generator(shapes)
    a, b, c = make(2 ** 31 + 11), make(2 ** 31 + 11), make(3)
    rate = np.exp(np.asarray(a["model.layers.1.linear_attn.A_log"]))
    assert rate.min() >= 1 and rate.max() <= 16
    step = np.log1p(np.exp(np.asarray(
        a["model.layers.1.linear_attn.dt_bias"], np.float64)))
    assert step.min() >= 0.99e-3 and step.max() <= 1.01e-1
    assert not np.asarray(a["model.layers.0.mlp.expert_tokens"]).any()
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    assert not np.array_equal(np.asarray(a["lm_head"]),
                              np.asarray(c["lm_head"]))


# -- the cut and the costs ----------------------------------------------------

def test_the_cut_holds_the_published_widths_and_1295_million_parameters():
    cfg = program_solar_open2.model_config(CONFIG)
    model, shapes = program_solar_open2.skeleton(cfg)
    n = sum(int(np.prod(shapes[k].shape)) for k, _ in
            model.named_parameters())
    assert n == 1295086144                       # x 8 bytes = 10.36 GB
    assert shapes["model.layers.0.mlp.router"].shape == (4096, 320)
    assert shapes["model.layers.0.mlp.experts_gate_up"].shape == (
        8, 4096, 2560)
    assert shapes["model.layers.1.linear_attn.qkv_proj"].shape == (
        4096, 3 * 64 * 128)
    assert shapes["model.layers.0.self_attn.qkv_proj"].shape == (
        4096, (64 + 16) * 128)
    assert shapes["lm_head"].shape == (4096, 24576)
    assert [hasattr(lyr, "self_attn") for lyr in model.model.layers] == [
        True, False, False, False]
    assert CONFIG["vocab_size"] == 196608 and CONFIG["reduced_from"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_rows": 196608}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Solar-Open2-250B")
        assert CONFIG["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in CONFIG["reduced"]:
                assert CONFIG[k] == v, k


def test_costs_by_hand():
    s = costs_solar_open2.sizes(CONFIG)
    assert (s["gqa"], s["kda"], s["held"], s["total"]) == (1, 3, 8, 320)
    gqa = 4096 * 10240 + 2 * 4096 * 8192
    kda = (4096 * 24576 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
           + 8192 * 4096)
    moe = 4096 * 320 + 3 * 4096 * 1280 * (1 + 8 * 8 / 320)
    per_token = gqa + 3 * kda + 4 * moe + 4096 * 24576
    assert costs_solar_open2.matmul_params_per_token(CONFIG) == per_token
    core = 64 * (64 * (3 * 128 + 2 * 128) + 6 * 128 * 128)
    assert costs_solar_open2.kda_core_per_token(CONFIG) == core
    assert costs_solar_open2.train_flops_per_token(CONFIG, 32768) == (
        6 * per_token + 6 * 64 * 128 * 32768 + 9 * core)
    flops, byts = costs_solar_open2.kda_core_train(CONFIG, 1, 32768)
    assert flops == 3 * 32768 * core and byts == 34 * 32768 * 8192
    flops, byts = costs_solar_open2.moe_experts_train(CONFIG, 6554)
    assert flops == 18 * 6554 * 4096 * 1280
    assert byts == 2 * (9 * 8 * 4096 * 1280 + 5 * 6554 * 4096)


# -- the new readers ----------------------------------------------------------

def _run(trace, counters=None, steps=1):
    run = {"kind": "train", "chips": 1, "steps_traced": steps,
           "peaks": PEAKS, "config": CONFIG, "batch_size": 1,
           "seq_len": 32768, "lower_s": 1.0, "counters": counters,
           "trace": None}
    if trace is not None:
        run["trace"] = {"dir": None, "scope_loaded": trace,
                        "scope_reduced": scope_reduce.reduce(trace)}
    return run


def _hand_written():
    """(trace, counters) of one step, written by hand."""
    ms = 1_000_000
    lyr = "jit(pure)/forward/model/layers/SolarOpen2DecoderLayer/"
    kda = lyr + "linear_attn/jvp(layers)/while/body/closed_call/checkpoint/"
    bwd = ("jit(pure)/backward/transpose(jvp(layers))/while/body/"
           "closed_call/checkpoint/")
    line = [["fusion.1", 0, 100 * ms, kda + "kda/core/while/body/dot_general"],
            ["fusion.2", 100 * ms, 20 * ms, kda + "kda/proj/dot_general"],
            ["fusion.3", 120 * ms, 10 * ms, kda + "kda/conv/mul"],
            ["fusion.4", 130 * ms, 200 * ms,
             bwd + "rematted_computation/kda/core/dot_general"],
            ["fusion.5", 330 * ms, 300 * ms, bwd + "kda/core/dot_general"],
            ["gmm.3", 630 * ms, 8 * ms, None],
            ["tgmm.1", 638 * ms, 8 * ms, None],
            ["fusion.6", 646 * ms, 2 * ms, lyr + "jvp(layers)/checkpoint/"
             "moe/experts/mul"],
            ["swiglu_fwd.8", 648 * ms, 22 * ms, None],
            ["fusion.7", 670 * ms, 5 * ms, lyr + "jvp(layers)/checkpoint/"
             "moe/router/dot_general"],
            ["fusion.8", 675 * ms, 25 * ms, lyr + "self_attn/attn/gate/mul"]]
    trace = {"device": {"/device:TPU:0": line},
             "spans": [["train_step", 0, 700 * ms]]}
    counters = {"expert_tokens": [[800, 838, 0, 819, 801, 830, 850, 816]] * 4,
                "dropped_pairs": 0}
    return trace, counters


def test_new_readers_on_a_hand_written_trace():
    run = _run(*_hand_written())
    got = {}
    for name in NEW:
        value, note = bench_run.layer_metric(name).compute(run)
        assert isinstance(note, str) and note
        got[name] = value
    assert got["linear_attn_ms_per_step"] == pytest.approx(630.0)
    assert got["moe_ms_per_step"] == pytest.approx(45.0)
    flops, byts = costs_solar_open2.kda_core_train(CONFIG, 1, 32768)
    least = max(3 * flops / 197e12, 3 * byts / 819e9)
    assert got["kda_core_roofline"] == pytest.approx(100 * least / 0.6)
    pairs = 800 + 838 + 819 + 801 + 830 + 850 + 816
    flops, byts = costs_solar_open2.moe_experts_train(CONFIG, pairs)
    least = costs.roofline_s(4 * flops, 4 * byts, PEAKS)[0]
    assert got["moe_experts_roofline"] == pytest.approx(100 * least / 0.018)
    assert 0 < got["moe_experts_roofline"] <= 100
    assert 0 < got["kda_core_roofline"] <= 100
    assert got["moe_expert_load_max_over_mean"] == pytest.approx(
        850 / (pairs / 8))
    assert got["moe_dropped_pairs"] == 0
    # the gate of the softmax layer is attention's under this table
    red, table = scope_tables.reduced(run, TABLE)
    assert scope_reduce.group_s(red, "attention", None, table) == \
        pytest.approx(0.025)
    assert red["component_s"][("kda/core", "recomputed")] == \
        pytest.approx(0.2)


def test_new_readers_with_nothing_to_read():
    """No trace, a trace without names (the parent's), no counters: None,
    and nothing raises."""
    old = json.load(open(os.path.join(HERE, "data", "trace_small.json")))
    unnamed = {"device": {p: [[n, s, d, None] for n, s, d in ev]
                          for p, ev in old["device"].items()},
               "spans": old["spans"]}
    for run in (_run(None), _run(unnamed)):
        for name in NEW:
            assert bench_run.layer_metric(name).compute(run) is None, name
    dense = _run(None, counters=None)
    dense["config"] = {"model_type": "llama"}
    assert bench_run.layer_metric("kda_core_roofline").compute(dense) is None


def check_manifest(m, root=None):
    """What this cell asks of a manifest `m` whose files lie under `root`
    (the module's `ROOT` as it stands at the call, not at the definition):
    by name and by membership, so that cells after it change nothing."""
    root = root or ROOT
    tiny = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-32k")
    assert len(cell["why"]) <= 200 and "1/40" in cell["why"]
    _, _, cell_file, config, traffic = bench_run.load_cell(root, CELL)
    assert traffic["kind"] == "pretrain" and traffic["seq_len"] == 32768
    assert cell_file["batch_size"] == 1
    assert set(cell_file["correct"]["limits"]) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap"}
    mine = {x["name"]: x for x in m["per_layer"]
            if CELL in x.get("workloads", [])}
    assert set(NEW) <= set(mine)
    # the tiny root lists every metric the cell is listed under
    assert set(mine) <= {x["name"] for x in tiny["per_layer"]}
    assert not {"mlp_ms_per_step", "mlp_roofline", "scope_coverage",
                "flash_attention_roofline"} & set(mine)
    bench_run.load_cell(DATA, TINY)
    table = scope_reduce.rules(os.path.join(ROOT, "chipbench", TABLE))
    from paddle_tpu.observability import scopes
    assert {r["scope"] for r in table["components"] if "scope" in r} <= (
        set(scopes.COMPONENTS) | set(scopes.PHASES))


def test_manifest_names_the_cell_and_the_tiny_root_mirrors_it():
    check_manifest(json.load(open(os.path.join(ROOT, "BENCHMARK.json"))))
