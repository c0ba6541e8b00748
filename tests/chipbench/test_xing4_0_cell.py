"""The `xing4_0` configuration's benchmark files (ISSUE 48) on the CPU at
tiny widths, from a data root of their own (`data_xing/`): the `pretrain`
driver end to end through its data files, `correct` seen to fail under the
control and under the cell's four faults (Sinkhorn cut short among them, a
model key read from the trainer settings), the reference's training steps
against autodiff of the whole, the generator's seeding of the
hyper-connection leaves, the cut's arithmetic at the published widths
against the catalog row, `costs_xing4_0` against a hand count, and the
three new readers on a small recorded trace and on runs with nothing to
read."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_xing")
sys.path.insert(0, ROOT)

from chipbench import costs  # noqa: E402
from chipbench import costs_glm4_moe_lite as cg  # noqa: E402
from chipbench import costs_xing4_0 as cx  # noqa: E402
from chipbench import program_xing4_0 as program  # noqa: E402
from chipbench import reference_xing4_0 as reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import scope_reduce, scope_tables  # noqa: E402
from chipbench.drivers import pretrain  # noqa: E402

CELL = "xing4.0-29b-a4b-ep8.pretrain-4k-batch"
TINY = "tiny-xing.pretrain"
TABLE = "components_xing4_0.json"
PEAKS = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
    "TPU v5 lite"]
CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "xing4.0-29b-a4b-ep8.json")))
B, S = 2, 4096
NEW = ("hc_ms_per_step", "hc_mix_roofline", "hc_res_sum_err")
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
FAULTS = ("update_not_applied", "learning_rate_doubled", "mtp_loss_left_out",
          "sinkhorn_cut_short")


def _ctx(seed=7, seconds=0.5):
    return bench_run.make_ctx(DATA, TINY, seed, seconds, require_chip=False,
                              t_start=time.perf_counter())[2]


def test_driver_finds_its_parts_and_reads_the_counters():
    assert pretrain.parts({"model_type": "xing4_0"}) == (
        program, reference, cx)
    ctx = _ctx()
    cfg = program.model_config(ctx.config)
    assert (cfg.experts_held, cfg.expert_offset, cfg.n_routed_experts) == (
        4, 4, 8)
    assert cfg.vocab_size == 256 and cfg.head_group == 2
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 6, 1e-6)
    assert cfg.rope_scaling == (64, 16, 32, 1, 1, 1)
    assert cfg.hc_init == (0.25, -1.0, 0.0, 1.0, -1.0)
    model, shapes = program.skeleton(cfg)
    names = [k for k, _ in model.named_parameters()]
    assert not any(k.endswith(("e_score_correction_bias", "res_sum_err",
                               "main_loss")) for k in names)
    assert sum(k.endswith("_hc.phi") for k in names) == 6
    with pytest.raises(ValueError, match="groups"):
        program.model_config(dict(ctx.config, n_group=8, topk_group=4))
    with pytest.raises(ValueError, match="rope_scaling"):
        program.model_config(dict(ctx.config, rope_scaling={"type": "ntk"}))
    with pytest.raises(ValueError, match="hc_seed"):
        program.model_config(dict(ctx.config, hc_seed=dict(
            program.HC_SEED, scale=0.01)))
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
    want = reference.loss_and_grads(
        f32, jnp.asarray(ids[0], jnp.int32), ctx.config,
        reference.held_of(ctx.config), "all")
    return got, want, {
        k: float(jnp.sqrt(jnp.sum(jnp.square(v))))
        for k, v in want["total"][1].items()}


def test_reference_train_step_reads_the_losses_of_the_whole(one_step):
    got, want, _ = one_step
    assert got["main_losses"][0] == pytest.approx(float(want["main"][0]),
                                                  rel=1e-6)
    assert got["mtp_losses"][0] == pytest.approx(float(want["mtp"][0]),
                                                 rel=1e-6)
    assert got["losses"][0] == pytest.approx(float(want["total"][0]),
                                             rel=1e-6)
    rows, cols = got["hc_res_sum_err"][0]
    assert 0 <= cols < 1e-5 and cols <= rows < 1e-2


@pytest.mark.parametrize("leaf", [
    "model.embed_tokens", "lm_head", "model.norm.weight",
    "model.layers.0.attn_hc.phi", "model.layers.0.mlp_hc.scale",
    "model.layers.1.attn_hc.bias", "model.layers.1.mlp_hc.phi",
    "model.layers.1.self_attn.kv_b_proj", "model.layers.1.mlp.router",
    "mtp.block.attn_hc.phi", "mtp.block.mlp_hc.bias", "mtp.eh_proj"])
def test_reference_train_step_is_autodiff_of_the_whole(one_step, leaf):
    """The half-layer-at-a-time backward with its expansions and sums: a
    leaf's first gradient norm is jax.grad's of the whole loss."""
    got, _, norms = one_step
    assert got["grad_norms"][leaf] == pytest.approx(norms[leaf], rel=2e-4)
    assert norms[leaf] > 0


def test_generator_seeds_the_hyper_connections_and_the_counters():
    cfg = program.model_config(_ctx().config)
    _, shapes = program.skeleton(cfg)
    make = program.generator(shapes)
    a, b = make(2 ** 31 + 11), make(2 ** 31 + 11)
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    for k in a:
        if k.endswith(program.ZEROS):
            assert not np.asarray(a[k]).any(), k
    hc = "mtp.block.mlp_hc."
    assert np.asarray(a[hc + "scale"]).tolist() == [0.25] * 3
    bias = np.asarray(a[hc + "bias"])
    assert bias[:4].tolist() == [-1.0] * 4 and bias[4:8].tolist() == [0.0] * 4
    res = bias[8:].reshape(4, 4)
    assert np.diag(res).tolist() == [1.0] * 4
    assert res[~np.eye(4, dtype=bool)].tolist() == [-1.0] * 12
    assert a[hc + "res_sum_err"].shape == (2,)
    phi = np.asarray(a[hc + "phi"], np.float32)
    assert phi.shape == (4 * 48, 24)
    assert phi.std() == pytest.approx(0.02, rel=0.1)
    assert (np.asarray(a["mtp.enorm.weight"]) == 1).all()
    bias = np.asarray(a["mtp.block.mlp.e_score_correction_bias"])
    assert 0 < np.abs(bias).max() < 0.1


# -- the cut and the costs ----------------------------------------------------

def test_the_cut_holds_the_published_widths_and_913_million_parameters():
    cfg = program.model_config(CONFIG)
    model, shapes = program.skeleton(cfg)

    def count(prefix):
        return sum(int(np.prod(shapes[k].shape)) for k, _ in
                   model.named_parameters() if k.startswith(prefix))

    attn = count("model.layers.0.self_attn.")
    assert attn == (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192
                    + 4096 * 3584) + 768 + 512 == 28409856 + 1280
    hc = count("model.layers.0.attn_hc.")
    assert hc == 24 * 14336 + 3 + 24
    dense = count("model.layers.0.") - attn - 2 * hc
    assert dense == 3 * 3584 * 9216 + 2 * 3584
    expert = count("model.layers.1.") - attn - 2 * hc
    assert expert == 3584 * 64 + 9 * 3 * 3584 * 1024 + 2 * 3584
    module = count("mtp.")
    assert module == attn + 2 * hc + expert + 2 * 3584 * 3584 + 3 * 3584
    total = count("")
    assert total == (5 * (attn + 2 * hc) + dense + 4 * expert + module
                     + 2 * 16384 * 3584 + 3584)
    assert 913e6 < total < 914e6           # 7.3 GB at 8 bytes a parameter
    for lyr in ("model.layers.0.self_attn.", "mtp.block.self_attn."):
        assert shapes[lyr + "q_a_proj"].shape == (3584, 768)
        assert shapes[lyr + "q_b_proj"].shape == (768, 32 * 192)
        assert shapes[lyr + "kv_a_proj"].shape == (3584, 512 + 64)
        assert shapes[lyr + "kv_b_proj"].shape == (512, 32 * 256)
        assert shapes[lyr + "o_proj"].shape == (32 * 128, 3584)
        assert lyr + "gate_proj" not in shapes
    assert shapes["model.layers.0.mlp.gate_up_proj"].shape == (3584, 18432)
    for lyr in ("model.layers.4.mlp.", "mtp.block.mlp."):
        assert shapes[lyr + "router"].shape == (3584, 64)
        assert shapes[lyr + "experts_gate_up"].shape == (8, 3584, 2048)
    assert shapes["model.layers.3.mlp_hc.phi"].shape == (14336, 24)
    assert shapes["model.embed_tokens"].shape == (16384, 3584)
    assert [type(lyr.mlp).__name__ for lyr in model.model.layers] == [
        "SwiGLUHalf"] + ["DroplessMoE"] * 4
    assert (cfg.head_group, cfg.moe_rows) == (8, 8192)
    assert 32 % cfg.head_group == 0


def test_every_key_not_reduced_is_the_catalog_rows():
    assert CONFIG["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "n_routed_experts", "vocab_rows"]
    assert {k: CONFIG["reduced_from"][k] for k in CONFIG["reduced"]} == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_rows": 131072}
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["vocab_rows"]) == (
        5, 1, 8, 16384)
    assert 16384 * 8 == 131072 and CONFIG["expert_offset"] == 0
    # head widths named and whole, none reduced
    for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "hidden_size", "hc_mult"):
        assert isinstance(CONFIG[k], int) and CONFIG[k] > 0
        assert k not in CONFIG["reduced"]
    assert len(CONFIG["assumed"]) >= 12 and CONFIG["mtp_loss_weight"] == 0.3
    assert sum("ASSUMED" in a for a in CONFIG["assumed"]) >= 5
    assert CONFIG["hc_seed"] == program.HC_SEED
    assert "EP8" in CONFIG["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Xing4.0-29B-A4B")
    assert CONFIG["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k not in CONFIG["reduced"]:
            assert CONFIG[k] == v, k
    assert CONFIG["model_type"] == "xing4_0"


def test_costs_by_hand():
    s = cx.sizes(CONFIG)
    assert (s["layers"], s["dense"], s["expert"], s["mtp"]) == (5, 1, 4, 1)
    assert cx.hc_halves(CONFIG) == 12 and cx.hc_map_width(CONFIG) == 24
    attn = (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584)
    assert cx.attention_params(CONFIG) == attn == 28409856
    moe = 3584 * 64 + 3 * 3584 * 1024 * (1 + 4 * 8 / 64)
    assert cx.matmul_params_per_token(CONFIG) == (
        6 * attn + 5 * moe + 3 * 3584 * 9216 + 2 * 3584 * 16384
        + 2 * 3584 * 3584 + 12 * 14336 * 24)
    flops, byts = cx.hc_mix_train(CONFIG, B, S)
    T, n, C, K = B * S, 4, 3584, 24
    fwd = 2 * n * C * K + 2 * n * C + 2 * n * C + 2 * (16 + 4) * C
    bwd = 4 * n * C * K + 4 * (16 + 4) * C + 4 * n * C + 4 * n * C
    assert flops == T * (fwd + bwd)
    # X read twice and X' written forward (3 n), u and y (2); backward dX',
    # X, the partial twice and X again (6 n), y, dy, du (3): 41 C elements
    assert byts == T * (9 * n + 5) * C * 2 == 8192 * 41 * 3584 * 2
    assert byts / T == pytest.approx(293888)             # ~294 kB a token
    assert costs.roofline_s(flops, byts, PEAKS)[1] == "memory"
    core, _ = cx.mla_core_train(CONFIG, 1, S)
    assert core == 3 * cx.causal_pairs(S) * 32 * 2 * (128 + 64 + 128)
    assert cx.mla_core_train is cg.mla_core_train
    assert cx.moe_experts_train(CONFIG, 4096)[0] == 18 * 4096 * 3584 * 1024
    per_token = cx.train_flops_per_token(CONFIG, S)
    assert per_token == pytest.approx(
        6 * cx.matmul_params_per_token(CONFIG) + 6 * core / S)
    # at 4096 positions the six cores are a fifth of the step's operations
    # (42 MFLOP a token a layer forward against 57 for the latent
    # projections: about a third of ATTENTION)
    assert 0.18 < 6 * core / S / per_token < 0.22
    assert core / 3 / S == pytest.approx(41.9e6, rel=0.01)
    # the mixing at the roofline: 12 halves of a ~0.4 s step
    least = 12 * costs.roofline_s(flops, byts, PEAKS)[0]
    assert 0.030 < least < 0.040


# -- the readers ----------------------------------------------------------------

def _run(trace, steps=1, counters=None):
    run = {"kind": "train", "chips": 1, "steps_traced": steps,
           "peaks": PEAKS, "config": CONFIG, "batch_size": B,
           "seq_len": S, "lower_s": 1.0, "counters": counters,
           "trace": None}
    if trace is not None:
        run["trace"] = {"dir": None, "scope_loaded": trace,
                        "scope_reduced": scope_reduce.reduce(trace)}
    return run


def _recorded():
    trace = json.load(open(os.path.join(DATA, "trace_xing.json")))
    return {"device": trace["device"], "spans": trace["spans"]}


def _read(name, run):
    value, note = bench_run.layer_metric(name).compute(run)
    assert isinstance(note, str) and note
    return value


# hc/map 3+2+5+2 forward, 5 recomputed, 7 backward; hc/pre 2+2 (kernels by
# name), 2 recomputed, 4 backward; hc/post 4+4 (kernels), 8+6 backward;
# hc/expand 1+1, hc/reduce 1
MAP, PRE, POST = 3 + 2 + 5 + 2 + 5 + 7, 2 + 2 + 2 + 4, 4 + 4 + 8 + 6


def test_hc_ms_reader_on_the_small_trace():
    value, note = bench_run.layer_metric("hc_ms_per_step").compute(
        _run(_recorded()))
    assert value == pytest.approx(MAP + PRE + POST + 2 + 1)
    assert "recomputed=7.000" in note and "hc/map=24.000" in note
    assert "hc/pre=10.000" in note and "hc/post=22.000" in note
    assert _read("hc_ms_per_step", _run(_recorded(), steps=2)) == (
        pytest.approx(value / 2))


def test_hc_mix_roofline_on_the_small_trace():
    flops, byts = cx.hc_mix_train(CONFIG, B, S)
    least, bound = costs.roofline_s(12 * flops, 12 * byts, PEAKS)
    assert bound == "memory"
    value, note = bench_run.layer_metric("hc_mix_roofline").compute(
        _run(_recorded()))
    assert value == pytest.approx(100 * least / ((MAP + PRE + POST) / 1e3))
    assert "bound=memory" in note and "12 half-layers" in note


def test_hc_res_sum_err_reads_the_steps_counter():
    run = _run(None, counters={"hc_res_sum_err": [3.5e-4, 9e-7],
                               "dropped_pairs": 0})
    value, note = bench_run.layer_metric("hc_res_sum_err").compute(run)
    assert value == 3.5e-4 and "9e-07" in note and "20 iterations" in note


def test_the_shared_readers_read_this_cell_through_its_own_files():
    run = _run(_recorded(), counters={
        "expert_tokens": [[512] * 8] * 5, "dropped_pairs": 0})
    for group in ("moe_experts", "moe", "hc", "hc_mix"):
        assert scope_tables.table_of(
            run, group, "components_solar_open2.json") == TABLE
    assert scope_tables.costs_of(run, "moe_experts_train",
                                 "costs_solar_open2") is cx
    assert scope_tables.costs_of(run, "hc_mix_train") is cx
    flops, byts = cx.moe_experts_train(CONFIG, 4096)
    least, _ = costs.roofline_s(5 * flops, 5 * byts, PEAKS)
    assert _read("moe_experts_roofline", run) == pytest.approx(
        100 * least / ((25 + 25 + 40) / 1e3))
    assert _read("moe_ms_per_step", run) == pytest.approx(25 + 25 + 40)
    assert _read("attention_ms_per_step", run) == pytest.approx(
        10 + 30 + 9 + 35)
    assert _read("head_loss_ms_per_step", run) == pytest.approx(30)
    assert _read("remat_recompute_ms_per_step", run) == pytest.approx(
        5 + 2 + 25)


def test_components_table_puts_the_paths_names_first():
    red, table = scope_tables.reduced(_run(_recorded()), TABLE)
    by = red["component_s"]
    assert by[("hc/map", "forward")] == pytest.approx(0.012)
    assert by[("hc/map", "recomputed")] == pytest.approx(0.005)
    assert by[("hc/pre", "forward")] == pytest.approx(0.004)
    assert by[("hc/post", "backward")] == pytest.approx(0.014)
    assert by[("hc/expand", "forward")] == pytest.approx(0.002)
    from paddle_tpu.observability import scopes
    assert {r["scope"] for r in table["components"] if "scope" in r} <= (
        set(scopes.COMPONENTS) | set(scopes.PHASES))
    glm = json.load(open(os.path.join(
        ROOT, "chipbench", "components_glm4_moe_lite.json")))
    assert table["components"][-len(glm["components"]):] == glm["components"]
    assert [r["component"] for r in table["components"][:7]] == [
        "hc/pre", "hc/post", "hc/map", "hc/pre", "hc/post", "hc/expand",
        "hc/reduce"]
    assert {g: glm["groups"][g] for g in glm["groups"]} == {
        g: table["groups"][g] for g in glm["groups"]}


@pytest.mark.parametrize("name", NEW)
def test_new_readers_with_nothing_to_read(name):
    """No trace and no counters, a trace without names (a program that
    names nothing), another architecture's run: None, and nothing raises."""
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
    """What this cell asks of a manifest `m` whose files lie under `root`:
    by name and by membership, so that cells after it change nothing."""
    root = root or ROOT
    tiny = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-4k-batch")
    assert len(cell["why"]) <= 200
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert config["name"] == "xing4.0-29b-a4b-ep8"
    assert config["reduced"] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"] and len(config["why"]) <= 200
    _, _, cell_file, config, traffic = bench_run.load_cell(root, CELL)
    assert pretrain.parts(config) == (program, reference, cx)
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
    assert cell_file["correct"]["controls"] == ["fp8"]
    assert cell_file["correct"]["faults"] == {
        "update_not_applied": {"learning_rate": 0.0},
        "learning_rate_doubled": {
            "learning_rate": 2 * config["trainer"]["learning_rate"]},
        "mtp_loss_left_out": {"mtp_loss_weight": 0.0},
        "sinkhorn_cut_short": {"hc_sinkhorn_iters": 1}}
    mine = {x["name"]: x for x in m["per_layer"]
            if CELL in x.get("workloads", [])}
    assert set(NEW) | set(OLD) <= set(mine)
    assert all(mine[n]["workloads"][0] == CELL for n in NEW)
    assert mine["hc_res_sum_err"]["layer"] == "residual path"
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
