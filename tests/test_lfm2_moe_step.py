"""`models/lfm2_moe.py` (ISSUE 51) through `jit.TrainStep`: two AdamW steps
against the plain reference's, half a layer at a time; the names, the taped
operations and the counters a trace of the step carries. (A file of its own
beside `test_lfm2_moe.py`: a test file is one worker's.)"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.observability import spans

import _compiled
from paddle_tpu.models.lfm2_moe import Lfm2MoeForCausalLM, lfm2_moe_tiny
from test_lfm2_moe import build, config_json, ids_of, ref, state_of

TRAINER = {"learning_rate": 3e-3, "beta1": 0.9, "beta2": 0.999,
           "epsilon": 1e-8, "weight_decay": 0.1}


def _step(model):
    opt = popt.AdamW(learning_rate=TRAINER["learning_rate"],
                     beta1=TRAINER["beta1"], beta2=TRAINER["beta2"],
                     epsilon=TRAINER["epsilon"],
                     parameters=model.parameters(),
                     weight_decay=TRAINER["weight_decay"])
    return paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))


def test_adamw_steps_through_train_step_follow_the_reference():
    """Two steps on two batches of two sequences: the losses and each
    leaf's change are the reference's, half a layer at a time, the tied
    table's gradient the sum of its two uses; an update not applied and a
    doubled rate (the faults the cell lists) move what they should."""
    model, cfg = build(seed=4)
    start = {k: jnp.array(v) for k, v in state_of(model).items()}
    batches = np.stack([ids_of(cfg, 11), ids_of(cfg, 12)])
    cj = config_json(cfg)
    want = ref.train_steps(lambda: dict(start), batches, cj, TRAINER)
    step = _step(model)
    got = []
    for ids in batches:
        x = paddle.to_tensor(ids)
        got.append(float(step(x, x).data))
    assert got == pytest.approx(want["losses"], rel=2e-5)
    assert step._traces == 1
    now = state_of(model)
    trained = {k for k, _ in model.named_parameters()}
    assert trained == set(want["grad_norms"]) == set(want["delta_norms"])
    for k in sorted(trained):
        d = float(jnp.sqrt(jnp.sum(jnp.square(now[k] - start[k]))))
        assert d == pytest.approx(want["delta_norms"][k], rel=2e-3), k
    c = model.moe_counters()
    assert c["expert_tokens"].shape == (3, 8)
    assert int(c["expert_tokens"].sum()) == 3 * 2 * 32 * 2
    assert not c["dropped_pairs"].any()
    assert 0 < want["expert_rows"] <= 32
    still = ref.train_steps(lambda: dict(start), batches, cj,
                            dict(TRAINER, learning_rate=0.0))
    assert max(still["delta_norms"].values()) == 0.0
    assert still["losses"][0] == want["losses"][0]
    fast = ref.train_steps(lambda: dict(start), batches, cj,
                           dict(TRAINER, learning_rate=6e-3))
    k = "model.layers.1.conv.in_proj"
    assert fast["delta_norms"][k] == pytest.approx(
        2 * want["delta_norms"][k], rel=0.05)


@pytest.fixture(scope="module")
def lowered():
    """Four sequences a batch, as the cell has it; the text and the set-up
    events alone are read, so the weights are zeros."""
    model = _compiled.shapes_only(
        lambda: Lfm2MoeForCausalLM(lfm2_moe_tiny()))
    step = _step(model)
    x = paddle.to_tensor(ids_of(model.cfg, 1, 4))
    spans.clear()       # the ring is bounded: a count taken before is no mark
    text = step.lower(x, x).as_text(debug_info=True)
    return text, spans.ring()


@pytest.mark.parametrize("name", [
    "conv/proj", "conv/core", "conv/out", "attn/qk_norm", "attn/rope",
    "attn/qkv", "attn/core", "attn/out", "moe/router", "moe/dispatch",
    "moe/experts", "moe/combine", "mlp", "head", "loss", "embed"])
def test_a_trace_carries_the_new_names(lowered, name):
    assert name in lowered[0]


def test_no_shared_expert_is_traced(lowered):
    assert "moe/shared" not in lowered[0]


def test_the_taped_halves_are_in_the_residuals_by_name(lowered):
    """`train_step.residuals`: a half keeps its input; the attention half
    also what the armed policy names of its kernel."""
    kept = {e["attrs"]["scope"]: e["attrs"] for e in lowered[1]
            if e.get("name") == "train_step.residuals"}
    ops = {k.split(":")[-1] for k in kept}
    assert {"lfm2_conv", "lfm2_attention", "lfm2_mlp", "moe_block",
            "head_loss"} <= ops
    one = 4 * 32 * 64 * 4                  # one [B, T, H] float32 array
    conv = next(v for k, v in kept.items() if k.endswith(":lfm2_conv"))
    # three conv layers, each its input alone (the leaves are the step's
    # own state): no B * X, no `bcx`, no y
    assert int(conv["bytes"]) == 3 * one
    rows = [e for e in lowered[1] if e.get("name") == "moe.rows"]
    assert len(rows) >= 3 and all(e["attrs"]["tokens"] == str(4 * 32)
                                  for e in rows)
