"""`models/xing4_0.py` (ISSUE 48) against its plain reference
(`chipbench/reference_xing4_0.py`) on seeded weights at tiny widths: logits,
both losses and every leaf's gradient, the hyper-connection leaves' among
them; a batch of three sequences against the three run alone; the YaRN
tables against the float64 formula; two AdamW steps through `TrainStep`
against the reference's half a layer at a time, the step's counter of
H_res's sums and the set-up event; the share test of the expert half. A
parity check runs its model, and the reference, under one `jit`
(`tests/_compiled.py`)."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import rope
from paddle_tpu.models import dots3_note, glm4_moe_lite, pieces
from paddle_tpu.models.xing4_0 import (Xing40Config, Xing40ForCausalLM,
                                       xing4_0_tiny)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _compiled  # noqa: E402
from chipbench import reference_xing4_0 as ref  # noqa: E402

B, T = 2, 32
HELD = (0, 8)
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


def config_json(cfg):
    """The configuration-file keys the reference reads, of a model config."""
    same = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "q_lora_rank", "kv_lora_rank", "rope_theta",
            "moe_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "rms_norm_eps", "vocab_size", "num_nextn_predict_layers",
            "mtp_loss_weight", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
            "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
    out = {k: getattr(cfg, k) for k in same}
    out["rope_scaling"] = None if cfg.rope_scaling is None else dict(
        zip(YARN_KEYS, cfg.rope_scaling), type="yarn")
    return out


def build(seed=0, **kw):
    """A tiny model whose norms, selection bias and hyper-connection biases
    are not at their initial values."""
    paddle.seed(seed)
    cfg = xing4_0_tiny(**kw)
    model = Xing40ForCausalLM(cfg)
    rng = np.random.default_rng(seed + 1)
    for k, t in model.state_dict().items():
        if k.endswith("norm.weight") or k.endswith("_hc.bias"):
            t.data = t.data + jnp.asarray(rng.normal(0, 0.1, t.data.shape),
                                          t.data.dtype)
        if k.endswith("_hc.phi"):       # m of order 1 at hidden 48
            t.data = t.data * 4.0
        if k.endswith("e_score_correction_bias"):
            t.data = jnp.asarray(rng.normal(0, 0.05, t.data.shape),
                                 t.data.dtype)
    return model, cfg


def state_of(model):
    return {k: t.data for k, t in model.state_dict().items()}


def ids_of(cfg, seed, batch=B):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, T)).astype(np.int32)


@pytest.fixture(scope="module")
def tiny():
    model, cfg = build()
    return model, cfg, config_json(cfg), ids_of(cfg, 7), state_of(model)


def test_the_layers_are_the_shared_ones_on_a_four_stream_path(tiny):
    model, cfg = tiny[:2]
    blocks = model.blocks()
    assert [type(b.mlp).__name__ for b in blocks] == [
        "SwiGLUHalf", "DroplessMoE", "DroplessMoE"]
    assert isinstance(model, glm4_moe_lite.Glm4MoeLiteForCausalLM)
    assert type(model.mtp) is glm4_moe_lite.MultiTokenPredictor
    assert model.model.streams == 4 and blocks[-1].of_module
    for b in blocks:
        assert type(b.self_attn) is dots3_note.LatentAttention
        assert b.self_attn.kind == dots3_note.CAUSAL
        for hc in (b.attn_hc, b.mlp_hc):
            assert type(hc) is pieces.HyperConnection
            assert hc.phi.shape == [4 * 48, 24]
            assert hc.scale.shape == [3] and hc.bias.shape == [24]
    with pytest.raises(ValueError):
        Xing40Config(hc_mult=1)


def test_logits_against_the_reference(tiny):
    model, _, cj, ids, state = tiny
    model.eval()
    got = paddle.jit.to_static(model)(paddle.to_tensor(ids)).data
    model.train()
    want = _compiled.reference(ref.logits, state, ids, cj, HELD)
    assert float(jnp.max(jnp.abs(got - want))) < 3e-6
    assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.fixture(scope="module")
def grads(tiny):
    model, cfg, cj, ids, state = tiny
    out = {"total": _compiled.loss_and_grads(model, model.loss, ids, ids)}
    out["kept"] = (float(model.main_loss.data), float(model.mtp_loss.data))
    out["errs"] = model.hc_counters()["res_sum_err"]
    want = _compiled.reference(ref.loss_and_grads, state, ids, cj, HELD,
                               "all")
    out.update({"want_" + k: v for k, v in want.items()})
    return out


def test_both_losses_against_the_reference(grads):
    main, extra = grads["kept"]
    assert main == pytest.approx(float(grads["want_main"][0]), rel=1e-6)
    assert extra == pytest.approx(float(grads["want_mtp"][0]), rel=1e-6)
    assert grads["total"][0] == pytest.approx(float(grads["want_total"][0]),
                                              rel=1e-6)
    assert abs(main - extra) > 1e-3


LEAVES = ["embed_tokens", "lm_head", "model.norm.weight",
          "input_layernorm.weight", "post_attention_layernorm.weight",
          "self_attn.q_a_proj", "self_attn.q_a_layernorm.weight",
          "self_attn.q_b_proj", "self_attn.kv_a_proj",
          "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj",
          "self_attn.o_proj", "mlp.gate_up_proj", "mlp.down_proj",
          "mlp.router", "mlp.experts_gate_up", "mlp.experts_down",
          "mlp.shared_gate_up", "mlp.shared_down", "attn_hc.phi",
          "attn_hc.scale", "attn_hc.bias", "mlp_hc.phi", "mlp_hc.scale",
          "mlp_hc.bias", "mtp.enorm.weight", "mtp.hnorm.weight",
          "mtp.eh_proj", "mtp.norm.weight"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_against_the_reference(grads, leaf):
    """jax.grad of the reference's whole loss: the trunk's leaves, the
    module's, and every half-layer's Phi, scales and biases."""
    got, want = grads["total"][1], grads["want_total"][1]
    names = [k for k in got if k.endswith(leaf)]
    assert names
    for k in names:
        w = np.asarray(want[k])
        assert np.abs(w).max() > 0, k
        assert np.abs(got[k] - w).max() <= 3e-4 * np.abs(w).max(), k


def test_the_steps_counter_is_the_references_own_sum_errors(tiny, grads):
    """The largest |row sum - 1| and |column sum - 1| of H_res over the
    step's tokens and its six half-layers, beside the reference's: the
    columns were normalised last."""
    _, _, cj, ids, state = tiny

    def errors(s, i):
        x, _, errs = ref.hidden_states(s, i, cj, HELD)
        g = ref.layer(ref._layer_w(s, ref.arch(cj), ref.MTP), ref.expand(
            ref.join(ref._up({k: s[n] for k, n in ref._JOIN.items()}),
                     s["model.embed_tokens"], x, i, ref.arch(cj).eps),
            ref.arch(cj)), ref.MTP, ref.arch(cj), HELD)[2]
        return jnp.max(jnp.concatenate(errs + [g]), axis=0)

    want = np.asarray(_compiled.reference(errors, state, ids))
    got = np.asarray(grads["errs"])
    assert got[1] < 1e-5 and want[1] < 1e-5
    assert got[0] > 10 * got[1]           # rows drift, columns are exact
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_batch_of_three_equals_the_three_run_alone(tiny):
    """`LatentAttention.branch` maps over sequences; the mixing, the
    experts and the heads are per token: the logits of three sequences in
    one batch are those of each alone."""
    model, cfg, _, _, _ = tiny
    ids = ids_of(cfg, 21, 3)
    model.eval()
    f = paddle.jit.to_static(model)
    whole = np.asarray(f(paddle.to_tensor(ids)).data)
    alone = np.concatenate([np.asarray(f(paddle.to_tensor(ids[i:i + 1])).data)
                            for i in range(3)])
    model.train()
    assert np.abs(whole - alone).max() < 3e-6
    assert np.abs(whole[0] - whole[1]).max() > 1e-2


def _yarn_by_hand(T_, d, theta, s, L0, fast, slow):
    """cos and sin [T, d/2] from the issue's formula, float64 throughout."""
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2 * i / d)
    dim = lambda b: d * math.log(L0 / (2 * math.pi * b)) / (
        2 * math.log(theta))
    low, high = max(math.floor(dim(fast)), 0), min(math.ceil(dim(slow)),
                                                   d // 2 - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = f * (1 - ramp) + f / s * ramp
    ang = np.arange(T_, dtype=np.float64)[:, None] * inv[None]
    return np.cos(ang), np.sin(ang), low, high


@pytest.mark.parametrize("d,L0,T_", [(64, 4096, 4096), (4, 16, 32)])
def test_yarn_tables_against_the_float64_formula(d, L0, T_):
    """The published rotary (d 64, theta 1e4, factor 64 from 4096, betas
    32 and 1) and the tiny preset's: program and reference make the same
    tables, the formula's to float32 rounding; the ramp lies inside the
    dims (some frequencies kept, some divided by 64)."""
    y = rope.Yarn(1e4, 64.0, L0, 32.0, 1.0, 1.0, 1.0)
    cos, sin, low, high = _yarn_by_hand(T_, d, 1e4, 64.0, L0, 32.0, 1.0)
    assert 0 <= low < high <= d // 2 - 1
    got_cos, got_sin = dots3_note._rope_tables(T_, d, y)
    np.testing.assert_array_equal(got_cos[:, :d // 2], got_cos[:, d // 2:])
    np.testing.assert_allclose(got_cos[:, :d // 2], cos, atol=1e-7)
    np.testing.assert_allclose(got_sin[:, :d // 2], sin, atol=1e-7)
    a = ref.Arch(*([0] * 10), theta=1e4, yarn=(64.0, L0, 32.0, 1.0, 1.0, 1.0),
                 **{k: 0 for k in ref.Arch._fields[12:]})
    r_cos, r_sin = ref.rope_tables(T_, d, a)
    np.testing.assert_array_equal(r_cos, got_cos[:, :d // 2])
    np.testing.assert_array_equal(r_sin, got_sin[:, :d // 2])
    inv = rope.inv_freq(d, y)
    plain = rope.inv_freq(d, 1e4)
    assert inv[0] == plain[0] and inv[-1] == pytest.approx(plain[-1] / 64)
    # mscale(64, 1) = 0.1 ln 64 + 1; the tables' factor is 1, the scores'
    # factor its square
    assert rope.yarn_mscale(64.0, 1.0) == pytest.approx(1.41589, abs=1e-5)
    assert rope.table_scale(y) == 1.0
    assert rope.softmax_scale(y, 192) == pytest.approx(
        1.41589 ** 2 / math.sqrt(192), rel=1e-5)
    assert ref.softmax_factor(a) == pytest.approx(1.41589 ** 2, rel=1e-5)
    # a plain base is what it was
    assert rope.softmax_scale(1e4, 192) == 1.0 / math.sqrt(192)
    assert rope.table_scale(1e4) == 1.0


def test_the_ffn_branch_summed_over_eight_shares_is_the_uncut_layers():
    """The share test: the expert half's branch y = FFN(RMSNorm(u)) of one
    layer, by the PROGRAM told each of the 8 shares in turn (one of 8
    experts each, router, bias and shared expert whole in every share),
    summed with the shared expert counted once, equals the REFERENCE's y
    for the layer that holds all 8."""
    model, cfg = build(seed=5)
    cj = config_json(cfg)
    a = ref.arch(cj)
    state = state_of(model)
    names = ref.layer_names(a, 1)
    w = ref._up({k: state[n] for k, n in names.items()})
    u = jnp.asarray(np.random.default_rng(6).normal(0, 1, (1, T, 48)),
                    jnp.float32)
    whole, sent = jax.jit(lambda w_, u_: ref.moe_branch(w_, u_, a, HELD))(
        w, u)
    assert int(sent.sum()) == T * 2
    shared = jax.jit(lambda w_, u_: ref._swiglu(
        ref._rms(u_, w_["ln2"], a.eps), w_["mlp.shared_gate_up"],
        w_["mlp.shared_down"], None))(w, u)
    total, rows = shared, 0
    for e0 in range(8):
        cfg_e = xing4_0_tiny(experts_held=1, expert_offset=e0)
        mlp = pieces.dropless_moe_of(cfg_e, selection_bias=True)
        full = model.model.layers[1].mlp
        mlp.e_score_correction_bias.data = full.e_score_correction_bias.data
        ws = [jnp.asarray(t.data) for t in full.weights()]
        ws = [t[e0:e0 + 1] if t.shape[:1] == (8,) and t.ndim == 3 else t
              for t in ws]
        assert [tuple(t.shape) for t in ws] == [
            tuple(t.shape) for t in mlp.weights()]
        y, counts, dropped = jax.jit(lambda u_, *ws_: mlp.compute(
            pieces.rms(u_, w["ln2"], a.eps), *ws_))(u, *ws)
        assert int(dropped) == 0
        total, rows = total + (y - shared), rows + int(counts.sum())
    assert rows == T * 2
    np.testing.assert_allclose(total, whole, atol=3e-6)
    assert float(jnp.max(jnp.abs(whole - shared))) > 1e-3


def test_keys_192_wide_reach_the_splash_kernel_padded_to_256(monkeypatch):
    """The published heads (128 no-rope + 64 rope | 128 value): 192 lanes
    are no multiple of 128, which the kernel's route refuses; the layer
    pads queries and keys with zeros to 256 (nothing added to q.k) and
    takes it, on the TPU route; GLM's 256-wide keys are not padded."""
    from paddle_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)

    def jaxpr_of(cfg):
        layer = _compiled.shapes_only(
            lambda: dots3_note.LatentAttention(cfg, dots3_note.CAUSAL))
        ws = [jnp.ones((cfg.hidden_size,), jnp.float32)] + [t.data for t in (
            layer.q_a_proj, layer.q_a_layernorm.weight, layer.q_b_proj,
            layer.kv_a_proj, layer.kv_a_layernorm.weight, layer.kv_b_proj,
            layer.o_proj)]
        x = jnp.zeros((1, 128, cfg.hidden_size), jnp.bfloat16)
        return str(jax.make_jaxpr(layer.block)(x, *ws))

    wide = dict(num_attention_heads=2, head_group=2, qk_rope_head_dim=64,
                v_head_dim=128, dtype="bfloat16")
    text = jaxpr_of(xing4_0_tiny(qk_nope_head_dim=128, **wide))
    assert "splash" in text and "bf16[128,2,256]" in text
    assert "pad[" in text
    text = jaxpr_of(xing4_0_tiny(qk_nope_head_dim=192, **wide))
    assert "splash" in text and "pad[" not in text
