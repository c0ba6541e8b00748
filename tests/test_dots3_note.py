"""`models/dots3_note.py` (ISSUE 33) against its plain reference
(`chipbench/reference_dots3_note.py`) on seeded weights at tiny widths, the
dense layer 0 included: logits, the loss and every leaf's gradient, which
loss reaches which leaf, the selected sets, the degenerate selections
(top-k and window of the whole sequence), the expert layer's shares with
the selection bias, and the model through `TrainStep`. The programs of
the models the benchmark already had are held to the parent's text."""
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import sparse_select_attention as dsa
from paddle_tpu.models.dots3_note import (FULL, SLIDING,
                                          Dots3NoteForCausalLM,
                                          dots3_note_tiny)
from paddle_tpu.nn.layer.moe import DroplessMoE, route_top_k

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import reference_dots3_note as ref  # noqa: E402

B, T = 2, 64
HELD = (0, 8)


def config_json(cfg):
    """The configuration-file keys the reference reads, of a model config."""
    same = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "q_lora_rank", "kv_lora_rank", "rope_theta",
            "swa_num_attention_heads", "swa_qk_nope_head_dim",
            "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_q_lora_rank",
            "swa_kv_lora_rank", "swa_rope_theta", "sliding_window_size",
            "index_n_heads", "index_head_dim", "index_topk",
            "moe_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "rms_norm_eps", "vocab_size")
    out = {k: getattr(cfg, k) for k in same}
    out["layer_types"] = list(cfg.layer_types)
    out["apply_mla_qkv_lora_rescale"] = cfg.mla_rescale
    return out


def build(seed=0, **kw):
    """A tiny model whose norms, biases and selection bias are not at their
    initial ones and zeros (8 index heads: with two, a quarter of the
    index scores are exactly 0 and the cut is a tie)."""
    paddle.seed(seed)
    cfg = dots3_note_tiny(index_n_heads=8, **kw)
    model = Dots3NoteForCausalLM(cfg)
    rng = np.random.default_rng(seed + 1)
    for k, t in model.state_dict().items():
        if k.endswith(("norm.weight", "k_norm_weight")):
            t.data = t.data + jnp.asarray(rng.normal(0, 0.1, t.data.shape),
                                          t.data.dtype)
        if k.endswith(("k_norm_bias", "e_score_correction_bias")):
            t.data = jnp.asarray(rng.normal(0, 0.05, t.data.shape),
                                 t.data.dtype)
    return model, cfg


@pytest.fixture(scope="module")
def tiny():
    model, cfg = build()
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size, (B, T)).astype(
        np.int32)
    state = {k: t.data for k, t in model.state_dict().items()}
    return model, cfg, config_json(cfg), ids, state


def test_layers_are_the_dense_one_then_a_period(tiny):
    model, cfg = tiny[:2]
    assert cfg.layer_types == (FULL, FULL, SLIDING, SLIDING, SLIDING)
    kinds = [(lyr.self_attn.kind, type(lyr.mlp).__name__,
              hasattr(lyr.self_attn, "indexer")) for lyr in model.model.layers]
    assert kinds == [(FULL, "Dots3NoteMLP", True),
                     (FULL, "DroplessMoE", True)] + [
                         (SLIDING, "DroplessMoE", False)] * 3
    # ONE body serves both kinds: the same class and the same methods
    assert len({type(lyr.self_attn) for lyr in model.model.layers}) == 1


def test_logits_against_the_reference(tiny):
    model, _, cj, ids, state = tiny
    model.eval()
    got = paddle.jit.to_static(model)(paddle.to_tensor(ids)).data
    model.train()
    with jax.default_matmul_precision("highest"):
        want = ref.logits(state, jnp.asarray(ids), cj, HELD)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.fixture(scope="module")
def grads(tiny):
    """The program's loss and gradients of L_LM + L_I, of L_LM alone and of
    the L_I alone, and the reference's of the whole."""
    model, _, cj, ids, state = tiny
    x = paddle.to_tensor(ids)
    out = {}
    for which in ("total", "lm", "indexer"):
        for p in model.parameters():
            p.grad = None
        if which == "total":
            loss = model.loss(x, x)
        else:
            lm, aux = model.losses(x, x)
            loss = lm if which == "lm" else aux[0] + aux[1]
        loss.backward()
        out[which] = (float(loss.data), {
            k: None if p.grad is None else np.asarray(p.grad.data)
            for k, p in model.named_parameters()})
    for p in model.parameters():
        p.grad = None
    out["want"] = ref.loss_and_grads(state, jnp.asarray(ids), cj, HELD)
    out["want_lm"] = ref.losses(state, jnp.asarray(ids), cj, HELD)
    return out


def test_loss_is_the_language_models_plus_the_indexers(tiny, grads):
    total, lm, li = (grads[k][0] for k in ("total", "lm", "indexer"))
    assert total == pytest.approx(lm + li, rel=1e-6)
    assert li > 0
    assert total == pytest.approx(float(grads["want"][0]), rel=1e-6)
    want_lm, want_li = grads["want_lm"]
    assert lm == pytest.approx(float(want_lm), rel=1e-6)
    assert li == pytest.approx(float(want_li), rel=1e-4)


LEAVES = ["embed_tokens", "lm_head", "model.norm.weight",
          "input_layernorm.weight", "post_attention_layernorm.weight",
          "self_attn.q_a_proj", "self_attn.q_a_layernorm.weight",
          "self_attn.q_b_proj", "self_attn.kv_a_proj",
          "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj",
          "self_attn.gate_proj", "self_attn.o_proj", "indexer.wq_b",
          "indexer.wk", "indexer.k_norm_weight", "indexer.k_norm_bias",
          "indexer.weights_proj", "mlp.gate_up_proj", "mlp.down_proj",
          "mlp.router", "mlp.experts_gate_up", "mlp.experts_down",
          "mlp.shared_gate_up", "mlp.shared_down"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_against_the_reference(tiny, grads, leaf):
    """jax.grad of the reference's whole loss. The indexer's leaves read
    the gradient of L_I with respect to the scores in bfloat16 (kept so
    from the forward): 2^-9 a number."""
    got, want = grads["total"][1], grads["want"][1]
    names = [k for k in got if k.endswith(leaf)]
    assert names
    tol = 4e-3 if "indexer" in leaf else 2e-4
    for k in names:
        w = np.asarray(want[k])
        assert np.abs(got[k] - w).max() <= tol * np.abs(w).max(), k


def test_each_loss_reaches_its_own_leaves_alone(grads):
    """The selection is hard and the indexer's inputs and target are
    constants: L_LM reaches no indexer leaf, L_I nothing else."""
    lm, li = grads["lm"][1], grads["indexer"][1]
    for k in lm:
        zero_lm = lm[k] is None or not np.abs(lm[k]).max()
        zero_li = li[k] is None or not np.abs(li[k]).max()
        assert (zero_lm, zero_li) == (("indexer" in k), ("indexer" not in k)), k


def test_selected_sets_are_the_references(tiny):
    """Both full layers, rows t < top-k among them: the program's mask from
    its own index inputs equals the reference's, pair for pair."""
    model, cfg, cj, ids, state = tiny
    want = ref.selected_sets(state, jnp.asarray(ids), cj, HELD)
    a = ref.arch(cj)
    x = jnp.take(state["model.embed_tokens"], jnp.asarray(ids), axis=0)
    for i in (0, 1):
        lyr = model.model.layers[i]
        sa = lyr.self_attn
        ws = [sa.q_a_proj.data, sa.q_a_layernorm.weight.data] + [
            t.data for t in sa.indexer.weights()]
        for b in range(B):
            qi, ki, w = sa._index_inputs(
                x[b], lyr.input_layernorm.weight.data, *ws)
            mask, _ = dsa.select_top_k(dsa.index_scores(qi, ki, w),
                                       cfg.index_topk)
            assert bool(jnp.all((mask != 0) == want[i][b])), (i, b)
            rows = np.asarray(mask).sum(1)
            assert (rows == np.minimum(np.arange(T) + 1, 16)).all()
        w = ref._up({k: state[n] for k, n in ref.layer_names(a, i).items()})
        with jax.default_matmul_precision("highest"):
            x = ref.layer(w, x, i, a, HELD)[0]
    counted = model.moe_counters()["attended_pairs"]
    assert counted.shape == (2,)


def test_selecting_and_windowing_the_whole_sequence_is_plain_causal_mla():
    """index_topk >= S and window >= S: every causal key is kept, and the
    logits are those of the same weights under a plain causal mask (the
    reference with its selection and window widened likewise, whose masks
    are the lower triangle)."""
    model, cfg = build(seed=3, index_topk=T, sliding_window_size=T)
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (1, T)).astype(
        np.int32)
    state = {k: t.data for k, t in model.state_dict().items()}
    cj = config_json(cfg)
    got = model(paddle.to_tensor(ids)).data
    with jax.default_matmul_precision("highest"):
        want = ref.logits(state, jnp.asarray(ids), cj, HELD)
        sets = ref.selected_sets(state, jnp.asarray(ids), cj, HELD)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    assert all(bool(jnp.all(m[0] == jnp.tril(jnp.ones((T, T), bool))))
               for m in sets)
    narrow, _ = build(seed=3)
    assert float(jnp.max(jnp.abs(
        narrow(paddle.to_tensor(ids)).data - got))) > 1e-4


# -- the expert layer with the selection bias --------------------------------------

def test_bias_selects_and_the_scores_alone_weigh():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (32, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.5, (16, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.5, (8,)), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(x @ w))
    top_i, top_w = route_top_k(x, w, 2, bias=bias)
    plain_i, _ = route_top_k(x, w, 2)
    assert (np.sort(top_i, 1) != np.sort(plain_i, 1)).any()
    for t in range(32):
        want = np.argsort(-(s[t] + np.asarray(bias)))[:2]
        assert set(want.tolist()) == set(np.asarray(top_i[t]).tolist())
        sel = s[t][np.asarray(top_i[t])]
        np.testing.assert_allclose(top_w[t], sel / sel.sum(), rtol=1e-5)
    g = jax.grad(lambda b: jnp.sum(route_top_k(x, w, 2, bias=b)[1]))(bias)
    assert not np.abs(g).max()


def test_shares_with_the_bias_add_up_to_the_uncut_layer():
    """The guide's share test: the four shares' partial results, the shared
    expert counted once, add up to what the layer that holds all eight
    experts gives; router and bias whole in every share."""
    paddle.seed(0)
    whole = DroplessMoE(32, 16, 8, 2, dtype="float32", selection_bias=True)
    whole.e_score_correction_bias.data = jnp.asarray(
        np.random.default_rng(1).normal(0, 0.1, (8,)), jnp.float32)
    x = paddle.to_tensor(np.random.default_rng(2).normal(
        0, 1, (48, 32)).astype(np.float32))
    want = whole(x).data
    shared = (jax.nn.silu(x.data @ whole.shared_gate_up.data[:, :16])
              * (x.data @ whole.shared_gate_up.data[:, 16:])
              ) @ whole.shared_down.data
    total = jnp.zeros_like(want)
    rows = 0
    for e0 in (0, 2, 4, 6):
        part = DroplessMoE(32, 16, 8, 2, experts_held=2, first_expert=e0,
                           dtype="float32", selection_bias=True)
        part.router.data = whole.router.data
        part.e_score_correction_bias.data = whole.e_score_correction_bias.data
        part.experts_gate_up.data = whole.experts_gate_up.data[e0:e0 + 2]
        part.experts_down.data = whole.experts_down.data[e0:e0 + 2]
        part.shared_gate_up.data = whole.shared_gate_up.data
        part.shared_down.data = whole.shared_down.data
        total = total + part(x).data - shared
        rows += int(part.expert_tokens.data.sum())
    assert rows == 48 * 2
    np.testing.assert_allclose(total + shared, want, atol=2e-6)
    no_bias = DroplessMoE(32, 16, 8, 2, dtype="float32")
    assert no_bias.e_score_correction_bias is None
    assert "e_score_correction_bias" not in no_bias.state_dict()


def test_model_told_its_share_matches_the_reference_told_the_same():
    model, cfg = build(seed=2, experts_held=4, expert_offset=4)
    ids = np.random.default_rng(9).integers(0, cfg.vocab_size, (1, T)).astype(
        np.int32)
    state = {k: t.data for k, t in model.state_dict().items()}
    cj = dict(config_json(cfg), n_routed_experts=4, expert_offset=4,
              reduced_from={"n_routed_experts": 8})
    assert state["model.layers.1.mlp.experts_down"].shape[0] == 4
    assert state["model.layers.1.mlp.router"].shape[1] == 8
    with jax.default_matmul_precision("highest"):
        want = ref.logits(state, jnp.asarray(ids), cj, (4, 4))
    assert float(jnp.max(jnp.abs(
        model(paddle.to_tensor(ids)).data - want))) < 2e-6


# -- through TrainStep -------------------------------------------------------------

def test_trains_through_train_step_without_retracing():
    model, cfg = build(seed=4)
    opt = popt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (1, T)).astype(
        np.int32))
    bias0 = np.asarray(model.model.layers[1].mlp.e_score_correction_bias.data)
    losses = [float(step(x, x).data) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.05 and np.isfinite(losses).all()
    assert step._traces == 1                       # train_step_retraces 0
    c = model.moe_counters()
    due = sum(min(t + 1, cfg.index_topk) for t in range(T))
    assert c["attended_pairs"].tolist() == [due, due]
    assert c["expert_tokens"].shape == (4, 8)
    assert int(c["expert_tokens"].sum()) == 4 * T * cfg.num_experts_per_tok
    assert not c["dropped_pairs"].any()
    # the selection bias is a buffer: the optimizer never sees it
    assert np.array_equal(bias0, np.asarray(
        model.model.layers[1].mlp.e_score_correction_bias.data))
    assert not any("e_score" in k for k, _ in model.named_parameters())


def test_step_carries_the_new_names():
    model, cfg = build(seed=5)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    x = paddle.to_tensor(np.zeros((1, T), np.int32))
    text = step.lower(x, x).as_text(debug_info=True)
    for name in ("attn/index", "attn/select", "attn/core/selected",
                 "attn/core/window", "attn/qkv", "attn/gate", "attn/rope",
                 "attn/out", "moe/router", "moe/experts"):
        assert name in text, name


def test_a_traced_step_leaves_one_moe_rows_event_an_expert_layer():
    """`moe.rows` (ISSUE 45): once an expert layer a trace, with the route
    the layer's scatter-adds took (the compiler's, off the chip) and the
    shape of its row buffer."""
    from paddle_tpu.observability import spans
    model, cfg = build(seed=5)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    x = paddle.to_tensor(np.zeros((1, T), np.int32))
    step._build()
    spans.clear()
    step._compiled.trace(*step._call_args((x, x)))
    events = [ev["attrs"] for ev in spans.ring() if ev["name"] == "moe.rows"]
    assert len(events) == cfg.num_hidden_layers - cfg.first_k_dense_replace
    assert all(ev["route"] == "xla" and ev["tokens"] == str(T)
               and ev["hidden"] == str(cfg.hidden_size)
               and int(ev["row_bytes"]) == 4 * cfg.hidden_size
               for ev in events)


# -- the models the benchmark had lower as they did --------------------------------

PARENT = {       # sha256 of the text at commit 0eb8308 (PR 32), read under
    # this suite's conftest (8 host devices), addresses and step tags out;
    # the two `llama_gqa.*` still PR 32's; the other four are PR 46's
    # program: `solar.*` with PR 37's delta-rule block and PR 45's row
    # moves (`kernels/row_moves.py`), and both models with the head + loss
    # that makes a block's gradients beside its loss (against commit
    # 4ccd5e4 their jaxprs differ in that one stretch and nowhere else)
    "llama_gqa.cpu_text": "b4b176201bc8d8fbafa942c340cb4a468ec2b616380afc060286c729b452eeeb",
    "solar.cpu_text": "828a6756db725ea97a7568847957159b50837da7a6c1d0b4ac2844606f3d0083",
    "granite.cpu_text": "557ddf2fb05388c761d8d5d4256b73f3c7542a3d10d555e0f35270360e53f8e0",
    "llama_gqa.tpu_jaxpr": "5324d891f9ab8d1d9e73a12910b401c5d8bf61db6d0ebfed52574e7c5fc20473",
    "solar.tpu_jaxpr": "479a45451596897a73798e72559cf1643c39dad67d8ff619922c6f5d09790152",
    "granite.tpu_jaxpr": "3643417b69b7f9a24fa25e40435d9c7bb2be836732cabf44334cc7170a6cd946",
}


def _existing(name):
    from paddle_tpu.models.granite_hybrid import (GraniteHybridForCausalLM,
                                                  granite_hybrid_tiny)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.solar_open2 import (SolarOpen2ForCausalLM,
                                               solar_open2_tiny)
    paddle.seed(0)
    if name == "llama_gqa":
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=96, hidden_size=256, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            dtype="float32"))
    if name == "solar":
        return SolarOpen2ForCausalLM(solar_open2_tiny(head_dim=64))
    return GraniteHybridForCausalLM(granite_hybrid_tiny())


@pytest.mark.parametrize("key", sorted(PARENT))
def test_existing_models_lower_to_the_parents_program(key, monkeypatch):
    """The Yi cells' model (LLaMA, GQA), Solar-Open2 and Granite through
    `TrainStep`: the StableHLO text of the CPU route, and the jaxpr of the
    TPU route (`flash_attention._on_tpu` patched: the splash wrapper with
    its window and value width unset), are the parent commit's, character
    for character (memory addresses in a repr apart)."""
    name, route = key.split(".")
    monkeypatch.setattr(fa, "_on_tpu", lambda: route == "tpu_jaxpr")
    model = _existing(name)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    x = paddle.to_tensor(np.zeros((1, 128), np.int32))
    if route == "tpu_jaxpr":
        step._build()
        text = str(step._compiled.trace(*step._call_args((x, x))).jaxpr)
    else:
        text = step.lower(x, x).as_text()
    # a step's executable tag counts the steps the process has built
    text = re.sub(r"0x[0-9a-f]+|train_step_\d+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[key]
