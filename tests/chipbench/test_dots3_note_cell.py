"""The `dots3_note` configuration's benchmark files (ISSUE 33) on the CPU at
tiny widths, from a data root of their own (`data_dots3/`): the `pretrain`
driver end to end through its data files, `correct` seen to fail under the
control, the reference's training steps against autodiff of the whole, the
cost arithmetic, the cut's arithmetic at the published widths against the
catalog row, and every new reader on a small trace and on runs with
nothing to read."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_dots3")
sys.path.insert(0, ROOT)

from chipbench import costs  # noqa: E402
from chipbench import costs_dots3_note as cd  # noqa: E402
from chipbench import program_dots3_note as program  # noqa: E402
from chipbench import reference_dots3_note as reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import scope_reduce, scope_tables  # noqa: E402
from chipbench.drivers import pretrain  # noqa: E402

CELL = "dots3-note-prev-ep32.pretrain-16k"
TINY = "tiny-dots3.pretrain"
TABLE = "components_dots3_note.json"
PEAKS = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
    "TPU v5 lite"]
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "dots3-note-prev-ep32.json")))
S = 16384
NEW = ("sparse_attn_ms_per_step", "latent_proj_ms_per_step",
       "dsa_index_roofline", "dsa_core_roofline", "window_attn_roofline",
       "dsa_selection_shortfall")
# readers the benchmark had, whose lists this cell joins: the last two
# rows since ISSUE 40, which made the tests that pinned those lists to
# their own cells ask for membership
OLD = ("device_idle_share.train", "train_mfu", "trace_lower_s",
       "step_host_ms", "train_step_retraces", "lower_forward_s",
       "lower_backward_s", "lower_optimizer_s", "lower_to_mlir_s",
       "lower_inner_compile_s",
       "attention_ms_per_step", "head_loss_ms_per_step",
       "optimizer_ms_per_step", "remat_recompute_ms_per_step",
       "moe_ms_per_step", "moe_expert_load_max_over_mean", "moe_dropped_pairs")


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


def test_driver_finds_its_parts_and_reads_the_counters():
    assert pretrain.parts({"model_type": "dots3_note"}) == (
        program, reference, cd)
    ctx = _ctx()
    cfg = program.model_config(ctx.config)
    assert (cfg.experts_held, cfg.expert_offset, cfg.n_routed_experts) == (
        4, 4, 8)
    assert cfg.vocab_size == 256 and cfg.head_group == 2
    model, _ = program.skeleton(cfg)
    names = [k for k, _ in model.named_parameters()]
    assert not any("e_score" in k or "attended" in k for k in names)
    rows = pretrain.counted(ctx, {"expert_tokens": [[1, 2]],
                                  "dropped_pairs": 0,
                                  "attended_pairs": [5]})
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
    want_loss, grads = reference.loss_and_grads(
        f32, jnp.asarray(ids[0], jnp.int32), ctx.config,
        reference.held_of(ctx.config))
    return got, float(want_loss), {
        k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in grads.items()}


def test_reference_train_step_reads_the_loss_of_the_whole(one_step):
    got, want_loss, grads = one_step
    assert got["losses"][0] == pytest.approx(want_loss, rel=1e-5)
    trained = {k for k in grads if not k.endswith(
        ("e_score_correction_bias", "expert_tokens", "dropped_pairs",
         "attended_pairs"))}
    assert set(got["grad_norms"]) == trained == set(got["delta_norms"])
    assert got["expert_rows"] > 0
    due = sum(min(t + 1, 16) for t in range(48))
    assert got["attended_pairs"] == [due, due]


@pytest.mark.parametrize("leaf", [
    "embed_tokens", "lm_head", "layernorm.weight", "self_attn.q_a_proj",
    "self_attn.q_b_proj", "self_attn.kv_a_proj", "self_attn.kv_b_proj",
    "self_attn.gate_proj", "self_attn.o_proj", "indexer.wq_b", "indexer.wk",
    "indexer.k_norm_weight", "indexer.k_norm_bias", "indexer.weights_proj",
    "mlp.gate_up_proj", "mlp.down_proj", "mlp.router", "mlp.experts_gate_up",
    "mlp.experts_down", "mlp.shared_gate_up", "mlp.shared_down",
    "model.norm.weight"])
def test_reference_train_step_is_autodiff_of_the_whole(one_step, leaf):
    """The gradient norms of a step taken half a layer at a time (inputs
    kept on the host, each full layer's L_I added where its layer is) are
    those of autodiff of the whole loss."""
    got, _, grads = one_step
    names = [k for k in grads if k.endswith(leaf)]
    assert names
    for name in names:
        assert got["grad_norms"][name] == pytest.approx(
            grads[name], rel=2e-4, abs=1e-9), name


def test_precompile_compiles_the_programs_train_steps_then_runs(tmp_path,
                                                                monkeypatch):
    """From shapes alone, on its own threads: what it leaves in JAX's
    persistent cache are the nine programs `train_steps` asks for (two
    kinds of mixer, the expert half and the dense half, forward and VJP,
    and the head + loss), key for key."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from chipbench import weights
    ctx = _ctx()
    cfg = program.model_config(ctx.config)
    _, shapes = program.skeleton(cfg)
    B, T = ctx.cell["batch_size"], ctx.traffic["seq_len"]
    big = ("jit__mixer_fwd", "jit__mixer_bwd", "jit__expert_fwd",
           "jit__expert_bwd", "jit__dense_fwd", "jit__dense_bwd",
           "jit__head_loss")

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


def test_generator_seeds_the_biases_small_and_the_counters_zero():
    cfg = program.model_config(_ctx().config)
    _, shapes = program.skeleton(cfg)
    make = program.generator(shapes)
    a, b, c = make(2 ** 31 + 11), make(2 ** 31 + 11), make(3)
    bias = np.asarray(a["model.layers.1.mlp.e_score_correction_bias"])
    assert bias.shape == (8,) and bias.dtype == np.float32
    assert 0 < np.abs(bias).max() < 0.1 and np.ptp(bias) > 0.01
    kb = np.asarray(a["model.layers.0.self_attn.indexer.k_norm_bias"])
    assert 0 < np.abs(kb).max() < 0.1
    assert (np.asarray(a["model.layers.0.self_attn.indexer.k_norm_weight"])
            == 1).all()
    for k in a:
        if k.endswith(("expert_tokens", "dropped_pairs", "attended_pairs")):
            assert not np.asarray(a[k]).any(), k
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    assert not np.array_equal(
        np.asarray(a["model.layers.1.mlp.e_score_correction_bias"]),
        np.asarray(c["model.layers.1.mlp.e_score_correction_bias"]))


# -- the cut and the costs ----------------------------------------------------

def test_the_cut_holds_the_published_widths_and_1466_million_parameters():
    cfg = program.model_config(CONFIG)
    model, shapes = program.skeleton(cfg)

    def count(prefix, trained_only=True):
        return sum(int(np.prod(shapes[k].shape)) for k, _ in
                   model.named_parameters() if k.startswith(prefix))

    assert count("") == 1465832192                  # x 8 bytes = 11.73 GB
    full = count("model.layers.0.self_attn.")
    sliding = count("model.layers.1.self_attn.")
    assert (full, sliding) == (144049920, 90834944)
    assert count("model.layers.0.self_attn.indexer.") == 9371904
    outside = count("model.layers.2.") - sliding
    assert outside == 213657600
    assert count("") == (full + 3 * sliding + 4 * outside
                         + 2 * 19008 * 5120 + 5120)
    buffers = [k for k in shapes if k.endswith("e_score_correction_bias")]
    assert len(buffers) == 4 and all(shapes[k].shape == (256,)
                                     for k in buffers)
    lyr = "model.layers.0.self_attn."
    assert shapes[lyr + "q_b_proj"].shape == (1024, 128 * 192)
    assert shapes[lyr + "kv_a_proj"].shape == (5120, 512 + 64)
    assert shapes[lyr + "kv_b_proj"].shape == (512, 128 * 256)
    assert shapes[lyr + "o_proj"].shape == (128 * 128, 5120)
    assert shapes[lyr + "gate_proj"].shape == (5120, 128)
    assert shapes[lyr + "indexer.wq_b"].shape == (1024, 64 * 128)
    swa = "model.layers.1.self_attn."
    assert shapes[swa + "q_b_proj"].shape == (1024, 64 * 256)
    assert shapes[swa + "kv_a_proj"].shape == (5120, 1024 + 64)
    assert shapes[swa + "kv_b_proj"].shape == (1024, 64 * 320)
    assert shapes[swa + "o_proj"].shape == (64 * 128, 5120)
    assert shapes["model.layers.3.mlp.router"].shape == (5120, 256)
    assert shapes["model.layers.3.mlp.experts_gate_up"].shape == (
        8, 5120, 2 * 1536)
    assert shapes["model.embed_tokens"].shape == (19008, 5120)
    assert shapes["lm_head"].shape == (5120, 19008)
    assert [lyr.self_attn.kind for lyr in model.model.layers] == [
        "full_attention"] + ["sliding_attention"] * 3
    assert all(type(lyr.mlp).__name__ == "DroplessMoE"
               for lyr in model.model.layers)
    assert (cfg.sliding_window_size, cfg.index_topk) == (513, 2048)


def test_every_key_not_reduced_is_the_catalog_rows():
    assert CONFIG["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "n_routed_experts", "vocab_rows"]
    assert {k: CONFIG["reduced_from"][k] for k in CONFIG["reduced"]} == {
        "num_hidden_layers": 46, "first_k_dense_replace": 1,
        "n_routed_experts": 256, "vocab_rows": 152064}
    assert len(CONFIG["reduced"]) <= 16 and len(CONFIG["assumed"]) >= 10
    assert set(CONFIG["kernels"]) >= {
        "window attention (splash)", "index scores", "selected core fwd",
        "selected core bwd", "grouped matmul", "swiglu fwd", "swiglu bwd"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "dots3-note-prev")
    assert CONFIG["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k not in CONFIG["reduced"]:
            assert CONFIG[k] == v, k       # layer_types whole among them
    assert CONFIG["layer_offset"] == 1 and len(CONFIG["layer_types"]) == 46
    assert CONFIG["vocab_size"] == row["config"]["vocab_size"] == 152064


def test_costs_by_hand():
    s = cd.sizes(CONFIG)
    assert (s["full"], s["sliding"], s["expert"], s["dense"]) == (1, 3, 4, 0)
    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
            + 5120 * 128 + 16384 * 5120)
    indexer = 1024 * 8192 + 5120 * 128 + 5120 * 64
    swa = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
           + 5120 * 64 + 8192 * 5120)
    assert cd.attention_params(CONFIG, "full_attention") == full + indexer
    assert cd.attention_params(CONFIG, "sliding_attention") == swa
    moe = 5120 * 256 + 3 * 5120 * 1536 * (1 + 8 * 8 / 256)
    assert cd.matmul_params_per_token(CONFIG) == (
        full + indexer + 3 * swa + 4 * moe + 5120 * 19008)
    assert cd.selected_pairs(CONFIG, S) == 31458304
    assert cd.causal_pairs(S) == 134225920
    assert cd.band_pairs(CONFIG, S) == 513 * 514 // 2 + (S - 513) * 513
    flops, byts = cd.dsa_core_train(CONFIG, 1, S)
    assert flops == 3 * 31458304 * 128 * 2 * (192 + 128)
    assert byts == S * 2 * (3 * 128 * 192 + 3 * (128 * 128 + 64)
                            + 6 * 128 * 128) + 8 * 31458304
    flops, byts = cd.window_attn_train(CONFIG, 1, S)
    assert flops == 3 * cd.band_pairs(CONFIG, S) * 64 * 2 * (256 + 128)
    flops, byts = cd.dsa_index_train(CONFIG, 1, S)
    assert flops == 3 * 134225920 * 64 * 2 * 128
    assert byts == S * 4 * (3 * (8192 + 128 + 64) + 2048)
    per_seq = (cd.dsa_core_train(CONFIG, 1, S)[0]
               + cd.dsa_index_train(CONFIG, 1, S)[0]
               + 3 * cd.window_attn_train(CONFIG, 1, S)[0])
    assert cd.train_flops_per_token(CONFIG, S) == pytest.approx(
        6 * cd.matmul_params_per_token(CONFIG) + per_seq / S)
    # a quarter of the causal pairs are kept at 8 x index_topk
    assert cd.selected_pairs(CONFIG, S) / cd.causal_pairs(S) == pytest.approx(
        0.2344, abs=1e-3)


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
    trace = json.load(open(os.path.join(DATA, "trace_dots3.json")))
    return {"device": trace["device"], "spans": trace["spans"]}


def _read(name, run):
    value, note = bench_run.layer_metric(name).compute(run)
    assert isinstance(note, str) and note
    return value


@pytest.mark.parametrize("name,want_ms", [
    ("sparse_attn_ms_per_step",
     8 + 30 + 12 + 40 + 15 + 5 + 35 + 45 + 10 + 25 + 20),
    ("latent_proj_ms_per_step", 20 + 3 + 10 + 30 + 7 + 12),
    ("attention_ms_per_step",            # through components.json, unedited:
     20 + 2 + 40 + 10 + 6 + 4 + 35 + 10 + 9 + 30 + 7 + 12),   # no Mosaic call
    ("moe_ms_per_step", 25 + 5 + 40),                        # without op_name
    ("head_loss_ms_per_step", 35), ("optimizer_ms_per_step", 20),
    ("remat_recompute_ms_per_step", 10 + 7)])
def test_ms_readers_on_the_small_trace(name, want_ms):
    assert _read(name, _run(_recorded())) == pytest.approx(want_ms)


@pytest.mark.parametrize("name,cost,calls,spent_s", [
    ("dsa_index_roofline", cd.dsa_index_train, 1,
     (8 + 30 + 15 + 5 + 25 + 20) / 1e3),
    ("dsa_core_roofline", cd.dsa_core_train, 1, (40 + 35 + 45 + 10) / 1e3),
    ("window_attn_roofline", cd.window_attn_train, 3, (6 + 4 + 9) / 1e3)])
def test_roofline_readers_on_the_small_trace(name, cost, calls, spent_s):
    """The least time for the required work over the component's device
    time, recomputation in the time, and under 100 %."""
    flops, byts = cost(CONFIG, 1, S)
    least, bound = costs.roofline_s(calls * flops, calls * byts, PEAKS)
    assert bound == "compute"
    got = _read(name, _run(_recorded()))
    assert got == pytest.approx(100 * least / spent_s)
    assert 0 < got < 100


def test_selection_shortfall_reads_the_steps_counter():
    due = cd.selected_pairs(CONFIG, S)
    exact = _run(None, counters={"attended_pairs": [due],
                                 "dropped_pairs": 0, "expert_tokens": [[1]]})
    assert _read("dsa_selection_shortfall", exact) == 0
    off = _run(None, counters={"attended_pairs": [due - 3]})
    assert _read("dsa_selection_shortfall", off) == 3
    assert _read("moe_dropped_pairs", exact) == 0


def test_components_table_tells_the_cores_apart():
    red, table = scope_tables.reduced(_run(_recorded()), TABLE)
    by = red["component_s"]
    assert by[("attn/core/selected", "forward")] == pytest.approx(0.040)
    assert by[("attn/core/selected", "backward")] == pytest.approx(0.080)
    assert by[("attn/core/selected", "recomputed")] == pytest.approx(0.010)
    assert by[("attn/core/window", "forward")] == pytest.approx(0.010)
    assert by[("attn/index", "backward")] == pytest.approx(0.045)
    assert by[("attn/select", "forward")] == pytest.approx(0.012)
    assert set(table["groups"]["sparse_attn"]) == {
        "attn/index", "attn/select", "attn/core/selected"}
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
    other = _run(_recorded(), counters={"attended_pairs": [1]})
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
    assert config["name"] == "dots3-note-prev-ep32"
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
    # one limit a checked step, the second the wider (ISSUE 40)
    first, second = limits["loss_gap"]["limit"]
    assert 0 < first < second
    assert cell_file["correct"]["controls"] == ["fp8"]
    mine = {x["name"]: x for x in m["per_layer"]
            if CELL in x.get("workloads", [])}
    # membership only: a later cell appends itself after this one
    assert set(NEW) | set(OLD) <= set(mine)
    # the tiny root lists every metric the cell is listed under
    assert set(mine) <= {x["name"] for x in tiny["per_layer"]}
    assert CELL in next(x for x in m["end_to_end"]
                        if x["name"] == "train_tokens_per_s_chip")["workloads"]
    assert all(os.path.exists(os.path.join(
        ROOT, "chipbench", "layer_metrics", n + ".py")) for n in mine)
    bench_run.load_cell(DATA, TINY)


def test_manifest_names_the_cell_and_the_tiny_root_mirrors_it():
    check_manifest(json.load(open(os.path.join(ROOT, "BENCHMARK.json"))))
