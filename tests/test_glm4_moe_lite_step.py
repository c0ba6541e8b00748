"""`models/glm4_moe_lite.py` (ISSUE 44) through `jit.TrainStep`: two AdamW
steps against the plain reference's, half a layer at a time with its
summed shared gradients, and the names and the set-up event a trace of the
step carries. (A file of its own beside `test_glm4_moe_lite.py`: a test
file is one worker's.)"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.observability import spans

import _compiled
from paddle_tpu.models.glm4_moe_lite import (Glm4MoeLiteForCausalLM,
                                             glm4_moe_lite_tiny)
from test_glm4_moe_lite import (B, T, build, config_json, ids_of, ref,
                                state_of)

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
    """Two steps on two batches of two sequences: the losses (the second
    depends on the first update of every leaf) and each leaf's change are
    the reference's, half a layer at a time with its summed shared
    gradients."""
    model, cfg = build(seed=4)
    start = {k: jnp.array(v) for k, v in state_of(model).items()}
    batches = np.stack([ids_of(cfg, 11), ids_of(cfg, 12)])
    want = ref.train_steps(lambda: dict(start), batches, config_json(cfg),
                           TRAINER)
    step = _step(model)
    got, kept = [], []
    for ids in batches:
        x = paddle.to_tensor(ids)
        got.append(float(step(x, x).data))
        kept.append((float(model.main_loss.data),
                     float(model.mtp_loss.data)))
    assert got == pytest.approx(want["losses"], rel=2e-5)
    assert [k[0] for k in kept] == pytest.approx(want["main_losses"],
                                                 rel=2e-5)
    assert [k[1] for k in kept] == pytest.approx(want["mtp_losses"],
                                                 rel=2e-5)
    assert step._traces == 1
    now = state_of(model)
    trained = {k for k, _ in model.named_parameters()}
    assert set(want["delta_norms"]) == trained == set(want["grad_norms"])
    for k in sorted(trained):
        d = float(jnp.sqrt(jnp.sum(jnp.square(now[k] - start[k]))))
        assert d == pytest.approx(want["delta_norms"][k], rel=2e-3), k
    c = model.moe_counters()
    assert c["expert_tokens"].shape == (3, 8)
    assert int(c["expert_tokens"].sum()) == 3 * B * T * 2
    assert not c["dropped_pairs"].any()
    # a fault the cell lists: the weight 0 in the trainer's settings leaves
    # the module's leaves with weight decay alone
    off = ref.train_steps(lambda: dict(start), batches[:1],
                          config_json(cfg), dict(TRAINER, mtp_loss_weight=0))
    assert off["losses"] == pytest.approx(want["main_losses"][:1], rel=1e-6)
    assert off["grad_norms"]["mtp.eh_proj"] == 0
    assert want["grad_norms"]["mtp.eh_proj"] > 0
    assert off["grad_norms"]["lm_head"] < want["grad_norms"]["lm_head"]


def test_a_trace_carries_the_modules_event_and_the_new_names_once():
    """One sequence a batch, as the cell has it, in a lowering of its own
    (two go through `jax.lax.map`, whose loop takes the names off the lines
    read here): the text alone is read, so the weights are zeros."""
    model = _compiled.shapes_only(
        lambda: Glm4MoeLiteForCausalLM(glm4_moe_lite_tiny()))
    step = _step(model)
    x = paddle.to_tensor(ids_of(model.cfg, 1, 1))

    def noted():
        return [e for e in spans.ring() if e.get("name") == "mtp.module"]

    before = len(noted())
    text = step.lower(x, x).as_text(debug_info=True)
    events = noted()[before:]
    assert len(events) == 1
    assert events[0]["attrs"] == {
        "depth": "1", "loss_weight": "0.3", "positions": str(T - 2),
        "shares_embedding": "True", "shares_head": "True",
        "block_kind": "moe"}
    for name in ("attn/core/causal", "attn/qkv", "attn/rope", "attn/out",
                 "mtp/embed", "mtp/proj", "mtp/block", "mtp/head",
                 "mtp/loss", "moe/router", "moe/experts", "mlp", "head",
                 "loss"):
        assert name in text, name
    # the block's inner scopes stay as they are inside the module's; the
    # head-group scan is a `while` under the module's name (its body is
    # lowered once for every layer, so what it holds is the module's by
    # lying inside that loop, which is how `mtp_ms_per_step` reads it)
    assert "mtp/block/" in text and "attn/gate" not in text
    inner = [ln for ln in text.splitlines() if "mtp/block" in ln]
    for name in ("attn/out", "attn/qkv", "moe/experts", "moe/router",
                 "while"):
        assert any(name in ln for ln in inner), name


# -- the model whose layers this one shares traces as it did ------------------

DOTS3_PARENT = {   # sha256 of the step's jaxpr, read under this suite's
    # conftest, addresses and step tags out: commit 45b00d7's (PR 43) but for
    # the expert layer, whose rows move through `kernels/row_moves.py` since
    # PR 45, and the head + loss, a block's gradients made beside its loss
    # since PR 46 (pinned again by each; PR 45 held these to 5013cc1e... /
    # 16205d76...)
    False: "da666e50546e68fd28f69ab2b4731de84f79b1312a6610435be2c577ad1980f7",
    True: "43fd7279ca8978e0dc2beb55777e0a3a6a8bbeb60ae5603e483d798066d3ce36",
}


@pytest.mark.parametrize("tpu_route", [False, True])
def test_dots3_notes_step_traces_to_the_parents_jaxpr(tpu_route, monkeypatch):
    """`LatentAttention` gained a third kind and `_linear_cross_entropy` a
    `scopes` argument: the dots3-note step (both of its kinds, its head +
    loss), on the CPU route and with `flash_attention._on_tpu` patched
    (head widths splash takes), is the parent commit's, character for
    character."""
    import hashlib
    import re

    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.models.dots3_note import (Dots3NoteForCausalLM,
                                              dots3_note_tiny)
    monkeypatch.setattr(fa, "_on_tpu", lambda: tpu_route)
    wide = dict(index_n_heads=8, swa_qk_nope_head_dim=60, swa_v_head_dim=64,
                v_head_dim=64, qk_nope_head_dim=60) if tpu_route else {}
    model = _compiled.shapes_only(
        lambda: Dots3NoteForCausalLM(dots3_note_tiny(**wide)))
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    x = paddle.to_tensor(np.zeros((1, 128), np.int32))
    step._build()
    text = str(step._compiled.trace(*step._call_args((x, x))).jaxpr)
    text = re.sub(r"0x[0-9a-f]+|train_step_\d+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() == DOTS3_PARENT[tpu_route]
