"""models/solar_open2.py against its plain reference
(`tests/reference/solar_open2.py`) on seeded weights at tiny widths: each
kind of layer and the whole model (logits, loss, every gradient leaf),
the counters the compiled step writes and the names it carries. The
operator is `tests/test_gated_delta_rule.py`'s, the expert layer and the
blocked head + loss `tests/test_dropless_moe.py`'s."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models import (SolarOpen2Config, SolarOpen2ForCausalLM,  # noqa: E402
                               solar_open2_tiny)
from reference import solar_open2 as ref  # noqa: E402


def cfg_json(c: SolarOpen2Config):
    """The configuration-file keys the reference reads, for a program
    config."""
    return dict(
        hidden_size=c.hidden_size, num_hidden_layers=c.num_hidden_layers,
        num_attention_heads=c.num_attention_heads,
        num_key_value_heads=c.num_key_value_heads, head_dim=c.head_dim,
        linear_attn_config=dict(
            short_conv_kernel_size=c.short_conv_kernel_size,
            head_dim=c.linear_head_dim, num_heads=c.linear_num_heads),
        kda_low_rank=c.kda_low_rank,
        moe_intermediate_size=c.moe_intermediate_size,
        n_routed_experts=c.experts_held or c.n_routed_experts,
        expert_offset=c.expert_offset,
        reduced_from={"n_routed_experts": c.n_routed_experts},
        num_experts_per_tok=c.num_experts_per_tok,
        norm_topk_prob=c.norm_topk_prob,
        routed_scaling_factor=c.routed_scaling_factor,
        rms_norm_eps=c.rms_norm_eps, gqa_layers=list(c.gqa_layers),
        vocab_size=c.vocab_size)


def _ids(c, batch, seq, seed=0):
    return np.random.default_rng(seed).integers(
        0, c.vocab_size, (batch, seq)).astype(np.int32)


def _params(m):
    return {k: t for k, t in m.named_parameters()}


def _against_reference(c, batch=2, seq=37, tol=2e-5):
    paddle.seed(3)
    m = SolarOpen2ForCausalLM(c)
    state = {k: t.data for k, t in m.state_dict().items()}
    ids = _ids(c, batch, seq)
    x = paddle.to_tensor(ids)
    cj = cfg_json(c)
    held = ref.held_of(cj)
    # the reference under jit: a program a call, not one an operation
    want = jax.jit(lambda s, i: ref.logits(s, i, cj, held))(state, ids)
    np.testing.assert_allclose(np.asarray(m(x).data), np.asarray(want),
                               atol=tol * float(jnp.max(jnp.abs(want))))
    loss = m.loss(x, x)
    loss.backward()
    want_loss, want_g = jax.jit(
        lambda s, i: ref.loss_and_grads(s, i, cj, held))(state, ids)
    assert float(loss.data) == pytest.approx(float(want_loss), rel=1e-5)
    leaves = _params(m)
    assert leaves and set(leaves) <= set(want_g)
    for name, t in leaves.items():
        assert t.grad is not None, name
        g = np.asarray(want_g[name])
        np.testing.assert_allclose(
            np.asarray(t.grad.data), g, atol=tol * max(np.abs(g).max(), 1e-6),
            err_msg=name)
    return m


# -- the layers and the model -------------------------------------------------

@pytest.mark.parametrize("kind", ["gqa", "kda"])
def test_one_layer_of_each_kind_matches_the_reference(kind):
    c = solar_open2_tiny(num_hidden_layers=1,
                         gqa_layers=(0,) if kind == "gqa" else ())
    m = _against_reference(c)
    names = {k.split(".")[3] for k in m.state_dict() if "layers.0" in k}
    assert ("self_attn" in names) == (kind == "gqa")
    assert ("linear_attn" in names) == (kind == "kda")


def test_kda_layer_through_the_conv_kernels_matches_the_reference(monkeypatch):
    """A delta-rule layer at the head width the convolution's kernels tile
    (128; the tiny configuration's 8 never reaches them), two groups of
    two heads, the kernels in the Pallas interpreter: `KDAttention` is
    held to the reference through the chip's route too."""
    from paddle_tpu.kernels import short_conv as sc
    through, fused = [], sc._fused

    def interpreted(pre, w, heads, _):
        through.append(pre.shape)
        return fused(pre, w, heads, True)

    monkeypatch.setattr(sc, "_on_tpu", lambda: True)
    monkeypatch.setattr(sc, "_fused", interpreted)
    _against_reference(solar_open2_tiny(
        num_hidden_layers=1, gqa_layers=(), linear_head_dim=128))
    assert through and set(through) == {(2, 37, 3 * 2 * 128)}


def test_whole_model_matches_the_reference_with_a_share_of_the_experts():
    """Four layers in the published pattern, 3 of 8 experts held from
    expert 2 on: logits, loss and every gradient leaf."""
    m = _against_reference(solar_open2_tiny(experts_held=3, expert_offset=2))
    kinds = ["self_attn" if hasattr(lyr, "self_attn") else "linear_attn"
             for lyr in m.model.layers]
    assert kinds == ["self_attn", "linear_attn", "linear_attn", "linear_attn"]
    counters = m.moe_counters()
    assert counters["expert_tokens"].shape == (4, 3)
    assert counters["dropped_pairs"].tolist() == [0, 0, 0, 0]


# -- the compiled step: counters and names ------------------------------------

def test_compiled_step_writes_the_counters_and_carries_the_scopes():
    import paddle_tpu.optimizer as popt
    c = solar_open2_tiny(experts_held=4, expert_offset=4, moe_rows=512)
    paddle.seed(5)
    m = SolarOpen2ForCausalLM(c)
    state = {k: jnp.array(t.data) for k, t in m.state_dict().items()}
    opt = popt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt, lambda i, l: m.loss(i, l))
    ids = _ids(c, 2, 48)
    x = paddle.to_tensor(ids)
    text = step.lower(x, x).compile().as_text()
    for name in ("kda/proj", "kda/conv", "kda/gate", "kda/core", "kda/out",
                 "attn/gate", "attn/core", "moe/router", "moe/dispatch",
                 "moe/experts", "moe/shared", "moe/combine", "head", "loss"):
        assert f"/{name}/" in text, name
    assert "rematted_computation" in text and "transpose(" in text
    loss0 = float(step(x, x).data)
    cj = cfg_json(c)
    _, sent = ref.hidden_states(state, jnp.asarray(ids), cj, ref.held_of(cj))
    counters = m.moe_counters()
    assert counters["expert_tokens"].tolist() == sent.sum(1).tolist()
    assert counters["dropped_pairs"].tolist() == [0] * 4
    assert float(step(x, x).data) < loss0
    assert step._traces == 1


def test_dropped_pairs_is_a_running_sum_over_steps():
    import paddle_tpu.optimizer as popt
    c = solar_open2_tiny(num_hidden_layers=1, gqa_layers=(0,), moe_rows=1)
    paddle.seed(6)
    m = SolarOpen2ForCausalLM(c)
    opt = popt.SGD(learning_rate=0.0, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt, lambda i, l: m.loss(i, l))
    x = paddle.to_tensor(_ids(c, 1, 32))
    step(x, x)
    once = m.moe_counters()
    short = int(once["expert_tokens"].sum()) - 1     # one row, 64 pairs
    assert short > 0 and once["dropped_pairs"].tolist() == [short]
    step(x, x)
    assert m.moe_counters()["dropped_pairs"].tolist() == [2 * short]
