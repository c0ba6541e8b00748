"""The compiled train step names its own work (ISSUE 25): model scopes on
device operations, phase spans and a trace counter in `TrainStep`, one
vocabulary (`observability/scopes.py`) that `chipbench/components.json`
resolves. CPU, tiny LLaMA, alone and under ZeRO-3 x TP on 4 virtual
devices."""
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.optimizer as popt  # noqa: E402
from chipbench import scope_reduce  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.observability import metrics, scopes, spans  # noqa: E402


def _step(sharded):
    plan = None
    if sharded:
        from paddle_tpu.distributed.sharding import ShardingPlan
        from paddle_tpu.distributed.topology import HybridCommunicateGroup
        hcg = HybridCommunicateGroup(dp_degree=1, sharding_degree=2,
                                     mp_degree=2, devices=jax.devices()[:4])
        plan = ShardingPlan(hcg.mesh, stage=3)
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    if plan is not None:
        # place the state on the mesh BEFORE the first trace: a step
        # traced on single-device arrays is traced again at its second
        # call, when its own outputs come back carrying the mesh (the
        # counter this file tests is what found that: PERF.md, PR 25)
        plan.materialize(model, opt)
    return paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l),
                                shard=plan)


def _batch(seq):
    rng = np.random.default_rng(seq)
    return paddle.to_tensor(rng.integers(0, 512, (2, seq)).astype("int32"))


@pytest.fixture(scope="module", params=[False, True],
                ids=["one-device", "zero3-x-tp-4-devices"])
def lowered(request):
    """(step, compiled text, wall seconds of lower(), set-up events)."""
    assert not metrics.enabled()           # FLAGS_metrics unset
    step = _step(request.param)
    x = _batch(32)
    spans.clear()
    t0 = time.perf_counter()
    low = step.lower(x, x)
    wall = time.perf_counter() - t0
    ring = spans.ring()
    return step, low.compile().as_text(), wall, ring, request.param


def test_every_named_instruction_resolves_to_a_component(lowered):
    _, text, _, _, sharded = lowered
    table = scope_reduce.rules()
    resolved, total, counts, missed = scope_reduce.text_coverage(text, table)
    # XLA:CPU drops the metadata of the fusions it wraps around single
    # operations; of the instructions that CARRY an op_name, all but the
    # partitioner's stray broadcasts resolve
    named_missed = [m for m in missed if m[2]]
    carrying = total - (len(missed) - len(named_missed))
    assert carrying > 100
    assert (carrying - len(named_missed)) / carrying >= 0.95, named_missed
    components = {c for c, _ in counts}
    assert {"attn/qkv", "attn/core", "attn/out", "mlp", "norm", "embed",
            "head", "loss", "optimizer", "layers"} <= components
    if sharded:
        assert {"tp_all_reduce", "tp_relayout", "zero3"} <= components


def test_backward_and_recomputed_are_told_apart(lowered):
    _, text, _, _, _ = lowered
    _, _, counts, _ = scope_reduce.text_coverage(text)
    for component in ("mlp", "attn/qkv", "head", "loss", "norm"):
        assert counts[(component, "forward")] > 0, component
        assert counts[(component, "backward")] > 0, component
    # the scanned stack is rematerialised: its recomputed operations
    # carry jax's marker, the head's and the loss's (outside it) do not
    assert sum(n for (c, d), n in counts.items() if d == "recomputed") > 0
    assert counts[("head", "recomputed")] == counts[("loss", "recomputed")] == 0
    assert counts[("optimizer", "update")] > 0


def test_setup_phases_are_in_the_ring_and_add_up(lowered):
    _, _, wall, ring, _ = lowered
    ph = scope_reduce.setup_phases(ring)
    assert ph["traces"] >= 1 and ph["retraces"] == 0
    assert ph["forward"] > 0 and ph["backward"] > 0 and ph["optimizer"] > 0
    assert ph["to_mlir"] > 0 and ph["trace"] >= ph["forward"]
    assert abs(ph["lower"] - wall) <= 0.1 * wall
    parts = (ph["call_args"] + ph["forward"] + ph["backward"]
             + ph["grad_sync"] + ph["optimizer"] + ph["to_mlir"]
             + ph["inner_compile"] + ph["inner_to_mlir"])
    assert abs(parts - wall) <= 0.1 * wall, ph
    assert all(ev["setup"] for ev in ring)         # nothing else recorded
    names = {ev["name"] for ev in ring}
    assert names <= set(scopes.SETUP) | {
        "train_step." + p for p in scopes.PHASES}


def test_a_new_batch_shape_raises_the_trace_counter(lowered):
    step, _, _, _, _ = lowered
    x = _batch(32)
    step(x, x)
    before = scope_reduce.setup_phases()
    step(x, x)                                     # same shapes: no trace
    assert scope_reduce.setup_phases()["traces"] == before["traces"]
    y = _batch(16)
    step.lower(y, y)
    after = scope_reduce.setup_phases()
    assert after["traces"] == before["traces"] + 1
    assert after["retraces"] == before["retraces"] + 1


def test_setup_events_survive_armed_per_call_spans():
    spans.clear()
    with spans.setup_span("train_step.lower", executable="t"):
        spans.setup_event("xla.backend_compile", 0.25, fun_name="f")
    spans.enable(True)
    try:
        spans.set_ring_size(4)
        for _ in range(20):
            with spans.span("testscopes.per_call"):
                pass
        ring = spans.ring()
    finally:
        spans.enable(False)
        spans.set_ring_size(512)
    pinned = [ev for ev in ring if ev.get("setup")]
    assert [ev["name"] for ev in pinned] == [
        "train_step.lower", "xla.backend_compile", "train_step.lower"]
    assert pinned[1]["within"] == "train_step.lower"
    assert len(ring) == 3 + 4
    spans.clear()


def test_scope_vocabulary_is_closed_and_is_what_components_json_resolves():
    with pytest.raises(ValueError):
        with scopes.scope("not-a-scope"):
            pass
    assert scopes.carried() is None
    with scopes.scope("mlp"):
        assert scopes.carried() == "mlp"
    # every scope components.json resolves is one the program can emit
    table = scope_reduce.rules()
    emitted = set(scopes.COMPONENTS) | set(scopes.PHASES)
    assert {r["scope"] for r in table["components"] if "scope" in r} <= emitted
    assert {r["scope"] for r in table["collectives"] if "scope" in r} <= (
        set(scopes.COLLECTIVES) | emitted)
    assert table["phases"] == list(scopes.PHASES)
    assert scope_reduce.PROGRAM_SPANS == scopes.STEP_SPANS
