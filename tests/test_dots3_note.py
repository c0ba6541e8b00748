"""`models/dots3_note.py` (ISSUE 33) against its plain reference
(`chipbench/reference_dots3_note.py`) on seeded weights at tiny widths, the
dense layer 0 included: logits, the loss and every leaf's gradient, which
loss reaches which leaf, the selected sets, the degenerate selections
(top-k and window of the whole sequence), the expert layer's shares with
the selection bias, and the model through `TrainStep`. A parity check
runs its model under one `jit` (`tests/_compiled.py`) and the step's three
tests read one `TrainStep`: a test file compiles what it checks once. (The
step programs' text is held to the parent's in
`tests/test_step_program_text.py`.)"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.kernels import sparse_select_attention as dsa
from paddle_tpu.models.dots3_note import (FULL, SLIDING,
                                          Dots3NoteForCausalLM,
                                          dots3_note_tiny)
from paddle_tpu.nn.layer.moe import DroplessMoE, route_top_k

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _compiled  # noqa: E402
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


def logits_of(model, ids):
    return _compiled.run(model, model, ids)


def test_logits_against_the_reference(tiny):
    """Through the public entry, `paddle.jit.to_static`: one program."""
    model, _, cj, ids, state = tiny
    model.eval()
    got = paddle.jit.to_static(model)(paddle.to_tensor(ids)).data
    model.train()
    want = _compiled.reference(ref.logits, state, ids, cj, HELD)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.fixture(scope="module")
def grads(tiny):
    """The program's loss and gradients of L_LM + L_I, of L_LM alone and of
    the L_I alone (a compiled program each), and the reference's of the
    whole."""
    model, _, cj, ids, state = tiny
    def indexers(i, l):
        first, second = model.losses(i, l)[1]
        return first + second

    out = {"total": _compiled.loss_and_grads(model, model.loss, ids, ids),
           "lm": _compiled.loss_and_grads(
               model, lambda i, l: model.losses(i, l)[0], ids, ids),
           "indexer": _compiled.loss_and_grads(model, indexers, ids, ids)}
    out["want"] = _compiled.reference(ref.loss_and_grads, state, ids, cj,
                                      HELD)
    out["want_lm"] = _compiled.reference(ref.losses, state, ids, cj, HELD)
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
    want = _compiled.reference(ref.selected_sets, state, ids, cj, HELD)
    a = ref.arch(cj)
    x = jnp.take(state["model.embed_tokens"], jnp.asarray(ids), axis=0)
    for i in (0, 1):
        lyr = model.model.layers[i]
        sa = lyr.self_attn
        ws = [sa.q_a_proj.data, sa.q_a_layernorm.weight.data] + [
            t.data for t in sa.indexer.weights()]
        selected = jax.jit(lambda xb, *ws: dsa.select_top_k(
            dsa.index_scores(*sa._index_inputs(xb, *ws)), cfg.index_topk)[0])
        for b in range(B):
            mask = selected(x[b], lyr.input_layernorm.weight.data, *ws)
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
    got = logits_of(model, ids)
    want = _compiled.reference(ref.logits, state, ids, cj, HELD)
    sets = _compiled.reference(ref.selected_sets, state, ids, cj, HELD)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    assert all(bool(jnp.all(m[0] == jnp.tril(jnp.ones((T, T), bool))))
               for m in sets)
    narrow, _ = build(seed=3)
    assert float(jnp.max(jnp.abs(logits_of(narrow, ids) - got))) > 1e-4


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
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (48, 32)),
                    jnp.float32)
    want = _compiled.run(whole, whole, x)
    shared = (jax.nn.silu(x @ whole.shared_gate_up.data[:, :16])
              * (x @ whole.shared_gate_up.data[:, 16:])
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
        total = total + _compiled.run(part, part, x) - shared
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
    want = _compiled.reference(ref.logits, state, ids, cj, (4, 4))
    assert float(jnp.max(jnp.abs(logits_of(model, ids) - want))) < 2e-6


# -- through TrainStep -------------------------------------------------------------

@pytest.fixture(scope="module")
def stepped():
    """One model and its `TrainStep`, traced once, by the lowering whose
    text (with the names) and set-up events the tests below read; the
    steps that train it run the same trace."""
    from paddle_tpu.observability import spans
    model, cfg = build(seed=4)
    opt = popt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    x = paddle.to_tensor(np.zeros((1, T), np.int32))
    spans.clear()
    text = step.lower(x, x).as_text(debug_info=True)
    events = [ev["attrs"] for ev in spans.ring() if ev["name"] == "moe.rows"]
    return model, cfg, step, text, events


def test_trains_through_train_step_without_retracing(stepped):
    model, cfg, step = stepped[:3]
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


def test_step_carries_the_new_names(stepped):
    text = stepped[3]
    for name in ("attn/index", "attn/select", "attn/core/selected",
                 "attn/core/window", "attn/qkv", "attn/gate", "attn/rope",
                 "attn/out", "moe/router", "moe/experts"):
        assert name in text, name


def test_a_traced_step_leaves_one_moe_rows_event_an_expert_layer(stepped):
    """`moe.rows` (ISSUE 45): once an expert layer a trace, with the route
    the layer's scatter-adds took (the compiler's, off the chip) and the
    shape of its row buffer."""
    _, cfg, _, _, events = stepped
    assert len(events) == cfg.num_hidden_layers - cfg.first_k_dense_replace
    assert all(ev["route"] == "xla" and ev["tokens"] == str(T)
               and ev["hidden"] == str(cfg.hidden_size)
               and int(ev["row_bytes"]) == 4 * cfg.hidden_size
               for ev in events)
