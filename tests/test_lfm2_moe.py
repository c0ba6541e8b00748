"""`models/lfm2_moe.py` (ISSUE 51) against its plain reference
(`chipbench/reference_lfm2_moe.py`) on seeded weights at tiny widths: logits,
the loss and every leaf's gradient (the taps', a head's q and k norms' and
the tied table's among them); a batch of three sequences against the three
run alone (a sequence's first rows read zeros, not the sequence before); the
q/k norm + rotary piece against a three-line form; the router's epsilon; the
share test of the expert half, with no shared expert to count once; the
count of the configuration file. A parity check runs its model, and the
reference, under one `jit` (`tests/_compiled.py`)."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import rope
from paddle_tpu.models import pieces
from paddle_tpu.models.lfm2_moe import (ATTENTION, CONV, Lfm2Attention,
                                        Lfm2MoeConfig, Lfm2MoeForCausalLM,
                                        ShortConv, lfm2_moe_tiny)
from paddle_tpu.nn.layer.moe import route_top_k

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import _compiled  # noqa: E402
from chipbench import reference_lfm2_moe as ref  # noqa: E402

B, T = 2, 32
HELD = (0, 8)


def config_json(cfg):
    """The configuration-file keys the reference reads, of a model config."""
    same = ("hidden_size", "num_hidden_layers", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads", "conv_L_cache",
            "rope_theta", "norm_eps", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "vocab_size", "intermediate_size")
    out = {k: getattr(cfg, k) for k in same}
    out["layer_types"] = list(cfg.layer_types)
    return out


def build(seed=0, **kw):
    """A tiny model whose norms and selection bias are not at their initial
    values."""
    paddle.seed(seed)
    cfg = lfm2_moe_tiny(**kw)
    model = Lfm2MoeForCausalLM(cfg)
    rng = np.random.default_rng(seed + 1)
    for k, t in model.state_dict().items():
        if k.endswith("norm.weight"):
            t.data = t.data + jnp.asarray(rng.normal(0, 0.1, t.data.shape),
                                          t.data.dtype)
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


def test_the_layers_follow_layer_types_and_num_dense_layers(tiny):
    model, cfg = tiny[:2]
    layers = model.model.layers
    assert [type(b.mlp).__name__ for b in layers] == [
        "SwiGLUHalf"] + ["DroplessMoE"] * 3
    assert [hasattr(b, "self_attn") for b in layers] == [
        False, False, True, False]
    assert type(layers[0].conv) is ShortConv
    assert type(layers[2].self_attn) is Lfm2Attention
    assert layers[1].conv.in_proj.shape == [64, 192]
    assert layers[1].conv.conv_weight.shape == [3, 64]
    assert layers[2].self_attn.q_layernorm.weight.shape == [16]
    moe = layers[1].mlp
    assert moe.shared_gate_up is None and moe.norm_topk_eps == 1e-6
    assert moe.e_score_correction_bias is not None
    assert "lm_head" not in model.state_dict()        # the table is the head
    full = Lfm2MoeConfig()
    assert [i for i, k in enumerate(full.layer_types) if k == ATTENTION] == [
        2, 6, 10, 14, 18, 21]
    assert full.layer_types.count(CONV) == 18 and full.head_dim == 64
    with pytest.raises(ValueError):
        Lfm2MoeConfig(num_hidden_layers=3, layer_types=(CONV, CONV))
    with pytest.raises(ValueError):
        lfm2_moe_tiny(layer_types=(CONV, CONV, "mamba", CONV))


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
    got = _compiled.loss_and_grads(model, model.loss, ids, ids)
    with jax.default_matmul_precision("highest"):
        want = ref.loss_and_grads(state, jnp.asarray(ids), cj, HELD)
    return got, want


def test_the_loss_against_the_reference(grads):
    got, want = grads
    assert got[0] == pytest.approx(float(want[0]), rel=1e-6)


LEAVES = ["embed_tokens", "model.norm.weight", "operator_norm.weight",
          "ffn_norm.weight", "conv.in_proj", "conv.conv_weight",
          "conv.out_proj", "self_attn.qkv_proj",
          "self_attn.q_layernorm.weight", "self_attn.k_layernorm.weight",
          "self_attn.out_proj", "mlp.gate_up_proj", "mlp.down_proj",
          "mlp.router", "mlp.experts_gate_up", "mlp.experts_down"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_against_the_reference(grads, leaf):
    """jax.grad of the reference's whole loss; the table's gradient is the
    sum of its two uses."""
    got, want = grads[0][1], grads[1][1]
    names = [k for k in got if k.endswith(leaf)]
    assert names
    for k in names:
        w = np.asarray(want[k])
        assert np.abs(w).max() > 0, k
        assert np.abs(got[k] - w).max() <= 3e-4 * np.abs(w).max(), k


def test_no_leaf_is_left_without_a_test(grads):
    got = grads[0][1]
    assert all(any(k.endswith(leaf) for leaf in LEAVES) for k in got)
    assert all(g is not None for g in got.values())


def test_a_batch_of_three_equals_the_three_run_alone(tiny):
    """The convolution pads each sequence with zeros and attention is a
    sequence's own: the logits of three sequences in one batch are those of
    each alone."""
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


def test_qk_norm_rope_is_a_heads_norm_then_rotate_half():
    """`pieces.qk_norm_rope` against the equations written out: RMSNorm
    over a head's channels with one weight for every head, then dim i
    paired with i + d/2 at angle t * theta^(-2i/d); bf16 in, one rounding."""
    rng = np.random.default_rng(3)
    d, S, theta, eps = 16, 12, 1e6, 1e-5
    q = rng.normal(0, 2, (2, S, 4, d)).astype(np.float32)
    k = rng.normal(0, 2, (2, S, 2, d)).astype(np.float32)
    wq, wk = (rng.normal(1, 0.2, d).astype(np.float32) for _ in range(2))

    def by_hand(x, w):
        x = x.astype(np.float64)
        y = x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w
        ang = np.arange(S)[:, None] * theta ** (-np.arange(0, d, 2) / d)
        cos, sin = (f(ang)[None, :, None, :] for f in (np.cos, np.sin))
        y1, y2 = y[..., :d // 2], y[..., d // 2:]
        return np.concatenate([y1 * cos - y2 * sin, y2 * cos + y1 * sin], -1)

    got_q, got_k = pieces.qk_norm_rope(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(wq), jnp.asarray(wk), eps,
                                       theta)
    np.testing.assert_allclose(got_q, by_hand(q, wq), atol=2e-6)
    np.testing.assert_allclose(got_k, by_hand(k, wk), atol=2e-6)
    # bf16 in, bf16 out, and no second rounding between norm and rotary
    qb = jnp.asarray(q, jnp.bfloat16)
    out = pieces.qk_norm_rope(qb, qb, jnp.asarray(wq), jnp.asarray(wk), eps,
                              theta)[0]
    assert out.dtype == jnp.bfloat16
    want = by_hand(np.asarray(qb, np.float32), wq)
    assert np.abs(np.asarray(out, np.float32) - want).max() <= 2 ** -8 * (
        np.abs(want).max())
    # the reference's own tables are the program's
    cos, sin = rope.tables(S, d, theta)
    r_cos, r_sin = ref._rope_tables(S, d, theta)
    np.testing.assert_array_equal(cos[:, :d // 2], r_cos)
    np.testing.assert_array_equal(sin[:, :d // 2], r_sin)


def test_the_routers_epsilon_is_an_argument_whose_default_changes_nothing():
    """`route_top_k(norm_eps=0)` is the program it was (the four accepted
    expert models' text); 1e-6 divides by the sum + 1e-6."""
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 16)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(0, 1, (16, 6)),
                    jnp.float32)
    text = lambda **kw: str(jax.make_jaxpr(
        lambda a, b: route_top_k(a, b, 2, **kw))(x, w))
    assert text() == text(norm_eps=0.0) != text(norm_eps=1e-6)
    assert text(norm_eps=1e-6).count(" add ") == text().count(" add ") + 1
    i0, w0 = route_top_k(x, w, 2)
    i1, w1 = route_top_k(x, w, 2, norm_eps=1e-6)
    np.testing.assert_array_equal(i0, i1)
    s = np.asarray(jax.nn.sigmoid(x @ w))
    top = np.take_along_axis(s, np.asarray(i0), -1)
    np.testing.assert_allclose(w1, top / (top.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 1.0, rtol=1e-6)
    assert (np.asarray(w1).sum(-1) < 1.0).all()


def test_the_ffn_branch_summed_over_four_shares_is_the_uncut_layers():
    """The share test: the expert half's branch y = FF(RMSNorm(u)) of one
    layer, by the PROGRAM told each of the 4 shares in turn (2 of 8 experts
    each, router and bias whole in every share, NO shared expert to count
    once), summed, equals the REFERENCE's y for the layer that holds all
    8."""
    model, cfg = build(seed=5)
    cj = config_json(cfg)
    a = ref.arch(cj)
    state = state_of(model)
    w = ref._up({k: state[n] for k, n in ref.layer_names(a, 1).items()})
    u = jnp.asarray(np.random.default_rng(6).normal(0, 1, (1, T, 64)),
                    jnp.float32)

    def uncut(w_, u_):
        return ref._moe(ref._part(w_, "mlp."), ref._rms(u_[0], w_["ln2"],
                                                        a.eps), a, HELD,
                        None, None)

    whole, sent = jax.jit(uncut)(w, u)
    assert int(sent.sum()) == T * 2
    total, rows = 0.0, 0
    full = model.model.layers[1].mlp
    for e0 in range(0, 8, 2):
        cfg_e = lfm2_moe_tiny(experts_held=2, expert_offset=e0)
        mlp = pieces.dropless_moe_of(cfg_e, selection_bias=True,
                                     norm_topk_eps=1e-6)
        assert mlp.shared_gate_up is None and len(mlp.weights()) == 3
        mlp.e_score_correction_bias.data = full.e_score_correction_bias.data
        ws = [jnp.asarray(t.data) for t in full.weights()]
        ws = [t[e0:e0 + 2] if t.shape[:1] == (8,) and t.ndim == 3 else t
              for t in ws]
        assert [tuple(t.shape) for t in ws] == [
            tuple(t.shape) for t in mlp.weights()]
        y, counts, dropped = jax.jit(lambda u_, *ws_: mlp.compute(
            pieces.rms(u_, w["ln2"], a.eps), *ws_))(u, *ws)
        assert int(dropped) == 0
        total, rows = total + y[0], rows + int(counts.sum())
    assert rows == T * 2
    np.testing.assert_allclose(total, whole, atol=3e-6)
    assert float(jnp.max(jnp.abs(whole))) > 1e-3


def test_the_configuration_file_holds_921_256_448_parameters():
    """The cut's arithmetic at the published widths: what the trainer holds
    (the parameters and the 8 selection-bias buffers of 32)."""
    from chipbench import program_lfm2_moe as program
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lfm2-8b-a1b-ep4.json")) as f:
        cfg_json = json.load(f)
    cfg = program.model_config(cfg_json)
    model, shapes = program.skeleton(cfg)

    def count(prefix, also=()):
        names = [k for k, _ in model.named_parameters()] + [
            k for k in shapes if k.endswith(also)]
        return sum(int(np.prod(shapes[k].shape)) for k in names
                   if k.startswith(prefix))

    bias = ("e_score_correction_bias",)
    conv = count("model.layers.1.conv.")
    assert conv == 2048 * 6144 + 2048 * 2048 + 3 * 2048 == 16_783_360
    attn = count("model.layers.2.self_attn.")
    assert attn == 2 * 2048 ** 2 + 2 * 2048 * 512 + 2 * 64 == 10_485_888
    assert count("model.layers.0.") == conv + 4096 + 3 * 2048 * 7168 == (
        60_827_648)
    expert = count("model.layers.1.mlp.", bias)
    assert expert == 8 * 3 * 2048 * 1792 + 2048 * 32 + 32 == 88_145_952
    assert count("model.embed_tokens") == 16384 * 2048
    assert count("", bias) == (
        60_827_648 + 6 * conv + 2 * attn + 8 * (4096 + expert)
        + 16384 * 2048 + 2048) == 921_256_448
    assert cfg.layer_types == (CONV, CONV, ATTENTION, CONV, CONV, CONV,
                               ATTENTION, CONV, CONV)
    assert (cfg.num_experts, cfg.experts_held, cfg.moe_rows,
            cfg.num_dense_layers) == (32, 8, 32768, 1)
