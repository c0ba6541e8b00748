"""chipbench on the CPU at tiny widths: every driver end to end through
its data files, the generators, the arithmetic, the reference against the
system, the manifest, and `correct` seen to fail: under the control (the
reference in a lower precision) and with the timed path broken."""
import json
import os
import re
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")          # a tiny benchmark: same layout
sys.path.insert(0, ROOT)

from chipbench import costs, harness, reference, trace_reduce  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import traffic, weights  # noqa: E402


def _run(cell, seed=7, seconds=0.6, trace=False):
    return bench_run.run_cell(DATA, cell, seed, seconds, trace,
                              require_chip=False,
                              t_start=time.perf_counter())


def _ctx(cell, seed=7, seconds=0.6):
    return bench_run.make_ctx(DATA, cell, seed, seconds, require_chip=False,
                              t_start=time.perf_counter())[2]


# -- the drivers, end to end through the data files --------------------------

@pytest.mark.parametrize("cell,metrics", [
    ("tiny-train.pretrain", {"train_tokens_per_s_chip", "setup_s"}),
    ("tiny-train-4dev.pretrain", {"train_tokens_per_s_chip", "setup_s"}),
    ("tiny-serve.chat", {"serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms",
                         "setup_s"}),
])
def test_cell_end_to_end(cell, metrics):
    out = _run(cell)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"     # named, never hidden
    json.dumps(out)


def test_traced_run_reports_layer_metrics_it_can_read():
    out = _run("tiny-serve.chat", trace=True)
    # no device plane on the CPU: the trace readers return nothing and
    # are left out; the host-clock and ledger readers report
    assert {"tick_ms", "queue_wait_p95_ms", "arrival_lag_p95_ms"} <= set(
        out["metrics"])
    assert "device_idle_share.serve" not in out["metrics"]


def test_run_without_the_chip_prints_no_result(capsys):
    assert bench_run.run_cell(DATA, "tiny-train.pretrain", 1, 0.1, False,
                              require_chip=True) is None
    assert "needs a TPU" in capsys.readouterr().err


# -- correct: the control and the broken path --------------------------------

def test_control_lower_precision_fails_train_limits():
    """The reference in fp8 in the program's place leaves the limits the
    tiny cell's file sets (the chip's readings are in PERF.md)."""
    ctx = _ctx("tiny-train.pretrain")
    from chipbench.drivers import train
    out = train.control(ctx)
    assert all(r["ok"] for r in out["sound"])
    assert not all(r["ok"] for r in out["fp8"])


def test_control_lower_precision_fails_serve_limit():
    ctx = _ctx("tiny-serve.chat", seconds=3.0)
    from chipbench.drivers import serve
    out = serve.control(ctx)
    assert all(r["ok"] for r in out["sound"])
    assert not all(r["ok"] for r in out["fp8"])


def test_broken_train_step_is_not_correct(monkeypatch):
    """A step that returns its state unchanged."""
    from paddle_tpu.jit import TrainStep
    orig = TrainStep.__call__

    def frozen(self, *batch):
        import jax.numpy as jnp
        before = {k: jnp.array(t.data, copy=True)   # the step donates
                  for k, t in self.model.state_dict().items()}
        loss = orig(self, *batch)
        for k, t in self.model.state_dict().items():
            t.data = before[k]
        return loss

    monkeypatch.setattr(TrainStep, "__call__", frozen)
    out = _run("tiny-train.pretrain")
    assert out["correct"] is False


def test_broken_serve_token_is_not_correct(monkeypatch):
    """A token altered where it is produced."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine as E
    orig = E._note_first_token

    def wrong(self, req):
        if len(req.output) == 1:
            req.output[0] = (req.output[0] + 1) % self.cfg.vocab_size
        return orig(self, req)

    monkeypatch.setattr(E, "_note_first_token", wrong)
    out = _run("tiny-serve.chat")
    assert out["correct"] is False


# -- the reference against the system and against itself ---------------------

def test_reference_backward_matches_autodiff_of_the_whole():
    """Layer-by-layer VJPs equal jax.grad through the whole model."""
    import jax
    import jax.numpy as jnp
    cfg = json.load(open(os.path.join(DATA, "bench/configs/tiny-train.json")))
    model, shapes = weights.skeleton(weights.model_config(cfg))
    state = weights.generator(shapes)(3)
    ids = weights.token_batches(3, cfg["vocab_size"], 1, 2, 32)
    a = reference.arch(cfg)

    def whole(p):
        x = jnp.take(p["model.embed_tokens"], ids[0], axis=0).astype(
            jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            w = {k: p[n].astype(jnp.float32)
                 for k, n in reference.layer_names(i).items()}
            x = reference._layer(w, x, a, None)
        lg = reference._mm(reference._rms(
            x[:, :-1], p["model.norm.weight"].astype(jnp.float32), a[3]),
            p["lm_head"].astype(jnp.float32), None)
        tgt = jnp.take_along_axis(lg, ids[0][:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(lg, -1) - tgt)

    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(whole)(
            {k: v.astype(jnp.float32) for k, v in state.items()})
    got = reference.train_steps(lambda: state, ids, cfg, cfg["trainer"])
    assert got["losses"][0] == pytest.approx(float(loss), rel=1e-5)
    for k, v in g.items():
        assert got["grad_norms"][k] == pytest.approx(
            float(jnp.sqrt(jnp.sum(v * v))), rel=2e-4), k


def test_reference_prefill_then_decode_through_the_cache():
    """The engine's served tokens, teacher-forced: each is the
    reference's best or within a rounding of it (serve.served_gaps)."""
    from chipbench.drivers import serve
    ctx = _ctx("tiny-serve.chat", seconds=1.0)
    sut = serve.build(ctx)
    d = serve.drive(ctx, sut, 6.0, 1.0)
    r = serve.readings(d)
    assert r["failed"] == 0 and len(r["finished"]) >= 3
    gap, _, n_tok = serve.served_gaps(sut["state"], ctx.config,
                                      r["finished"][:4])
    assert n_tok > 10 and gap < 0.05


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, leaf = reference.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 2e-9}, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    assert reference.worst_leaf_gap(
        {"a": float("nan"), "b": 2.0, "c": 0.0}, ref)[0] == float("inf")


# -- traffic, percentiles, the knee ------------------------------------------

def test_traffic_same_seed_same_requests_other_seed_same_sizes():
    mix = json.load(open(os.path.join(ROOT, "chipbench/traffic/chat.json")))
    a = traffic.serve_requests(mix, 4.0, 40, 1000, 5)
    b = traffic.serve_requests(mix, 4.0, 40, 1000, 5)
    c = traffic.serve_requests(mix, 4.0, 40, 1000, 2 ** 31 + 11)
    assert len(a) == 160
    assert all(x[0] == y[0] and x[2] == y[2] and (x[1] == y[1]).all()
               for x, y in zip(a, b))
    assert sorted(len(x[1]) for x in a) == sorted(len(x[1]) for x in c)
    assert sorted(x[2] for x in a) == sorted(x[2] for x in c)
    assert [len(x[1]) for x in a] != [len(x[1]) for x in c]
    lens = [len(x[1]) for x in a]
    assert min(lens) >= 32 and max(lens) <= 3072
    assert 450 < np.median(lens) < 580
    assert a[-1][0] == pytest.approx(40.0, rel=1e-6)   # mean gap 1/rate
    assert all(t1 >= t0 for (t0, _, _), (t1, _, _) in zip(a, a[1:]))


def test_shared_prefix_and_burst_mixes_need_no_code():
    mix = {"arrival": {"process": "gamma", "cv": 3.0},
           "prompt_len": {"dist": "fixed", "value": 64, "min": 64, "max": 64},
           "output_len": {"dist": "uniform", "min": 4, "max": 8},
           "shared_prefix": {"documents": 2, "len": 48}}
    reqs = traffic.serve_requests(mix, 10.0, 10, 500, 1)
    assert (reqs[0][1][:48] == reqs[2][1][:48]).all()
    assert not (reqs[0][1][:48] == reqs[1][1][:48]).all()
    gaps = np.diff([0.0] + [r[0] for r in reqs])
    assert gaps.std() / gaps.mean() > 2.0


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (95, 3.85),
                                    (100, 4.0)])
def test_percentile_is_numpys(q, want):
    assert harness.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
    assert want == pytest.approx(np.percentile([4.0, 1.0, 3.0, 2.0], q))


def test_knee_and_fixed_rate():
    sweep = [
        {"rate": 2, "arrived": 60, "completed": 60, "queue_mid": 0,
         "queue_end": 0},
        {"rate": 4, "arrived": 120, "completed": 118, "queue_mid": 1,
         "queue_end": 1},
        {"rate": 8, "arrived": 240, "completed": 200, "queue_mid": 9,
         "queue_end": 30},
        {"rate": 6, "arrived": 180, "completed": 175, "queue_mid": 2,
         "queue_end": 5},
    ]
    assert traffic.knee(sweep) == 4
    assert traffic.fixed_rate(4) == 3.0
    assert traffic.fixed_rate(11.3) == 9.0
    assert traffic.knee(sweep[2:3]) is None


# -- the trace reduction ------------------------------------------------------

def test_trace_reduce_union_self_time_and_gaps():
    us = 1000
    trace = {"device": {"/device:TPU:0": [
        ("while.1", 10 * us, 60 * us),           # holds the next two
        ("fusion.3", 12 * us, 20 * us),
        ("all-gather.7", 40 * us, 10 * us),
        ("fusion.9", 80 * us, 10 * us),
    ]}, "spans": [("engine.step", 0, 75 * us), ("engine.step", 78 * us,
                                                22 * us)]}
    red = trace_reduce.reduce(trace)
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"] == pytest.approx(70e-6)
    assert red["idle_share"] == pytest.approx(0.30)
    assert red["op_self_s"]["while.1"] == pytest.approx(30e-6)
    assert red["collective_exposed_s"] == pytest.approx(10e-6)
    assert dict(red["op_s"])["fusion"] == pytest.approx(30e-6)
    gaps = dict(red["idle_by_span_s"])
    assert gaps["engine.step"] == pytest.approx(20e-6)   # [0,10], [90,100]
    assert gaps["between spans"] == pytest.approx(10e-6)  # [70,80]
    assert trace_reduce.breakdown(red)["device_ops"][0][0] in (
        "while", "fusion")


def test_trace_reduce_on_a_recorded_chip_trace():
    """A cut of a trace recorded on the v5e chip in this PR (the first
    3000 device events of `yi-6b-1chip.pretrain`, eight steps' spans)."""
    trace = json.load(open(os.path.join(DATA, "trace_small.json")))
    trace["device"] = {k: [tuple(e) for e in v]
                       for k, v in trace["device"].items()}
    trace["spans"] = [tuple(s) for s in trace["spans"]]
    red = trace_reduce.reduce(trace)
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert sum(red["op_self_s"].values()) == pytest.approx(
        red["busy_s"], rel=1e-6)                 # self times partition busy
    assert any("splash" in n for n in red["op_self_s"])
    assert len(trace_reduce.breakdown(red)["device_ops"]) == 10


# -- costs against hand-worked values ----------------------------------------

YI = {"hidden_size": 4096, "intermediate_size": 11008,
      "num_attention_heads": 32, "num_key_value_heads": 4,
      "num_hidden_layers": 4, "vocab_size": 64000}


def test_costs_train_step():
    # a layer: qkv 4096 x (32+8) x 128, o 4096 x 4096, three 4096 x 11008
    layer = 4096 * 5120 + 4096 * 4096 + 3 * 4096 * 11008
    assert layer == 173_015_040
    assert costs.matmul_params(YI) == 4 * layer + 4096 * 64000
    assert costs.total_params(YI) == (4 * layer + 2 * 4096 * 64000
                                      + 9 * 4096)
    assert costs.train_flops_per_token(YI, 4096) == (
        6 * (4 * layer + 262_144_000) + 6 * 4 * 4096 * 4096)


def test_costs_flash_and_ragged_kernels():
    flops, byts = costs.flash_attention_train(YI, 1, 4096)
    # one causal product: 2 x 32 heads x 4096^2 x 128 / 2; six of them
    assert flops == 6 * 32 * 4096 * 4096 * 128
    q, kv = 4096 * 32 * 128 * 2, 4096 * 4 * 128 * 2
    assert byts == (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv)
    cfg = {"hidden_size": 2048, "num_attention_heads": 16,
           "num_key_value_heads": 8}
    # a decode row over 1000 cached positions and a 3-row chunk ending
    # at position 10 (rows see 8, 9, 10)
    flops, byts = costs.ragged_attention_call(cfg, [(1, 1000), (3, 10)])
    assert flops == 4 * 16 * 128 * (1000 + 27)
    assert byts == 2 * (2 * 1010 * 8 * 128 + 2 * 4 * 16 * 128)
    peaks = json.load(open(os.path.join(ROOT, "chipbench/peaks.json")))
    t, bound = costs.roofline_s(197e12, 1.0, peaks["TPU v5 lite"])
    assert (t, bound) == (1.0, "compute")
    assert costs.roofline_s(1.0, 819e9, peaks["TPU v5 lite"]) == (
        1.0, "memory")


# -- the manifest and the data files ------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_manifest(m, root, whole=False):
    """What every manifest `m` whose files lie under `root` has to hold;
    `whole` for the benchmark's own (its command, paths and chips)."""
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in m[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert set(x.get("workloads", [])) <= cells
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert all(0 < x["bound"] <= 0.1 for x in m["end_to_end"])

    def reports(cell, metric):
        return cell in e2e[metric].get("workloads", cells)

    for w in m["workloads"]:
        bench_run.load_cell(root, w["name"])          # every file is there
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        assert any(reports(w["name"], k) for k in e2e if k != "setup_s")
    for x in m["per_layer"]:
        mod = bench_run.layer_metric(x["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
            x["layer"], x["unit"], x["moves"])
        assert x["name"].endswith("_roofline") == (
            "roofline" in x["name"]) and ("roofline" not in x["name"]
                                          or x["unit"] == "%")
        for cell in x.get("workloads", cells):
            assert reports(cell, x["moves"]), (x["name"], cell)
    if whole:
        assert m["command"] == ["python3", "chipbench/run.py"]
        assert m["paths"] == ["chipbench", "tests/chipbench"]
        assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
            1, len(m["workloads"]) // 4)


@pytest.mark.parametrize("root", [ROOT, DATA])
def test_manifest_names_files_and_moves(root):
    check_manifest(json.load(open(os.path.join(root, "BENCHMARK.json"))),
                   root, whole=root == ROOT)


def test_run_py_names_no_cell_config_mix_or_metric():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    src = open(os.path.join(ROOT, "chipbench/run.py")).read()
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    names += [w["traffic"] for w in m["workloads"]]
    assert [n for n in names if n in src] == []


LATENT_WIDTHS = ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def check_head_widths(f):
    """A configuration file that names its head widths itself (`head_dim`,
    or the three of latent attention) is held to those: positive whole
    numbers that `reduced` does not list, whatever `hidden_size` over the
    head count comes to (2048 over 20 heads of 192 + 64 and 256 is a
    published model); one that names none states them by that quotient,
    which then has to be whole."""
    named = [k for k in ("head_dim",) + LATENT_WIDTHS if k in f]
    if "head_dim" in f or set(LATENT_WIDTHS) <= set(f):
        for k in named:
            assert type(f[k]) is int and f[k] > 0, (k, f[k])
            assert k not in f["reduced"], k
    else:
        assert f["hidden_size"] % f["num_attention_heads"] == 0, named


def test_configuration_files_carry_their_cut():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in m["configs"]:
        f = json.load(open(os.path.join(ROOT, c["file"])))
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert f["assumed"] and f["deployment"]
        check_head_widths(f)
        for k in f["reduced"]:
            assert not re.search(r"(_dim|_rank|_size)$", k), k


LATENT = {"hidden_size": 2048, "num_attention_heads": 20, "reduced": [],
          "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256}


@pytest.mark.parametrize("f,holds", [
    (LATENT, True),
    ({"hidden_size": 2048, "num_attention_heads": 20, "reduced": []}, False),
    ({"hidden_size": 2048, "num_attention_heads": 20, "reduced": [],
      "head_dim": 128}, True),
    ({"hidden_size": 4096, "num_attention_heads": 32,
      "reduced": ["num_hidden_layers"]}, True),
    # two of the three say nothing of the third: the quotient stands
    ({k: v for k, v in LATENT.items() if k != "v_head_dim"}, False),
    (dict(LATENT, reduced=["v_head_dim"]), False),
    (dict(LATENT, qk_rope_head_dim=0), False),
    (dict(LATENT, v_head_dim=256.5), False),
], ids=["latent-2048-over-20", "no-width-2048-over-20", "head_dim-named",
        "no-width-whole-quotient", "latent-without-v", "width-reduced",
        "width-zero", "width-not-whole"])
def test_head_width_rule(f, holds):
    if holds:
        check_head_widths(f)
    else:
        with pytest.raises(AssertionError):
            check_head_widths(f)


def test_peaks_table_is_keyed_by_exact_device_kind():
    peaks = json.load(open(os.path.join(ROOT, "chipbench/peaks.json")))
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v5" not in peaks and "cpu" not in peaks
