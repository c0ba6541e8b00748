"""`models/xing4_0.py` (ISSUE 48) through `jit.TrainStep`: two AdamW steps
against the plain reference's, half a layer at a time, with the step's
counter of H_res's sums and the faults the cell lists; the names and the
set-up events a trace of the step carries. (A file of its own beside
`test_xing4_0.py`: a test file is one worker's.)"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.observability import spans

import _compiled
from paddle_tpu.models.xing4_0 import Xing40ForCausalLM, xing4_0_tiny
from test_xing4_0 import T, build, config_json, ids_of, ref, state_of

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
    """Two steps on two batches of two sequences: the losses, each leaf's
    change and the counter of H_res's sums are the reference's, half a
    layer at a time; the faults the cell lists move what they should."""
    model, cfg = build(seed=4)
    start = {k: jnp.array(v) for k, v in state_of(model).items()}
    batches = np.stack([ids_of(cfg, 11), ids_of(cfg, 12)])
    want = ref.train_steps(lambda: dict(start), batches, config_json(cfg),
                           TRAINER)
    step = _step(model)
    got, errs = [], []
    for ids in batches:
        x = paddle.to_tensor(ids)
        got.append(float(step(x, x).data))
        errs.append(model.hc_counters()["res_sum_err"])
    assert got == pytest.approx(want["losses"], rel=2e-5)
    assert step._traces == 1
    np.testing.assert_allclose(errs, want["hc_res_sum_err"], atol=1e-5)
    now = state_of(model)
    trained = {k for k, _ in model.named_parameters()}
    # the half-layers fed an expansion: two of their three maps move nothing,
    # and the reference compares their first gradient alone
    entry = {f"{b}.attn_hc.{k}" for b in ("model.layers.0", "mtp.block")
             for k in ("phi", "scale", "bias")}
    assert trained == set(want["grad_norms"])
    assert set(want["delta_norms"]) == trained - entry
    assert entry == ref.entry_path_leaves(ref.arch(config_json(cfg)),
                                          [0, 1, ref.MTP])
    for k in sorted(trained - entry):
        d = float(jnp.sqrt(jnp.sum(jnp.square(now[k] - start[k]))))
        assert d == pytest.approx(want["delta_norms"][k], rel=2e-3), k
    c = model.moe_counters()
    assert c["expert_tokens"].shape == (2, 8)
    assert not c["dropped_pairs"].any()
    # Sinkhorn cut short, a model key read from the trainer settings: one
    # iteration leaves the rows further from 1 and moves the first loss
    cut = ref.train_steps(lambda: dict(start), batches[:1], config_json(cfg),
                          dict(TRAINER, hc_sinkhorn_iters=1))
    assert cut["hc_res_sum_err"][0][0] > 3 * want["hc_res_sum_err"][0][0]
    assert abs(cut["losses"][0] - want["losses"][0]) > 1e-4
    assert want["grad_norms"]["mtp.block.attn_hc.phi"] > 0


def test_a_trace_carries_the_streams_event_and_the_new_names_once():
    """Two sequences a batch, as the cell has it; the text alone is read,
    so the weights are zeros."""
    model = _compiled.shapes_only(lambda: Xing40ForCausalLM(xing4_0_tiny()))
    step = _step(model)
    x = paddle.to_tensor(ids_of(model.cfg, 1, 2))

    def noted(name):
        return [e for e in spans.ring() if e.get("name") == name]

    before = len(noted("hc.streams")), len(noted("mtp.module"))
    text = step.lower(x, x).as_text(debug_info=True)
    events = noted("hc.streams")[before[0]:]
    assert len(events) == 1 and len(noted("mtp.module")) == before[1] + 1
    at = events[0]["attrs"]
    assert (at["n"], at["iterations"], at["halves"]) == ("4", "4", "6")
    assert at["stream_array_bytes"] == str(4 * 2 * T * 48 * 4)
    assert at["kept_one_stream_bytes"] == str(2 * T * 48 * 4)
    assert "X" in at["attention_half_keeps"] and "X" in at["ffn_half_keeps"]
    for name in ("hc/map", "hc/pre", "hc/post", "hc/expand", "hc/reduce",
                 "attn/core/causal", "attn/qkv", "mtp/block", "moe/experts",
                 "mlp", "head", "loss"):
        assert name in text, name
    # the module's layer is a whole four-stream layer under the module's name
    inner = [ln for ln in text.splitlines() if "mtp/block" in ln]
    for name in ("hc/map", "hc/pre", "hc/post", "hc/expand", "hc/reduce"):
        assert any(name in ln for ln in inner), name
