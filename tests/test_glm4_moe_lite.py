"""`models/glm4_moe_lite.py` (ISSUE 44) against its plain reference
(`chipbench/reference_glm4_moe_lite.py`) on seeded weights at tiny widths:
both losses, every leaf's gradient and AdamW steps through `TrainStep`; 20
heads on a hidden size they do not divide, the head-group sizes, the
shares of experts and vocabulary against the uncut layer and loss, what
the multi-token-prediction module does to the shared leaves, and the names
a trace of the step carries. A parity check runs its model, and the
reference, under one `jit` (`tests/_compiled.py`)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import dots3_note
from paddle_tpu.models.dots3_note import CAUSAL
from paddle_tpu.models.glm4_moe_lite import (Glm4MoeLiteForCausalLM,
                                             glm4_moe_lite_tiny)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _compiled  # noqa: E402
from chipbench import reference_glm4_moe_lite as ref  # noqa: E402

B, T = 2, 32
HELD = (0, 8)


def config_json(cfg):
    """The configuration-file keys the reference reads, of a model config."""
    same = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "q_lora_rank", "kv_lora_rank", "rope_theta",
            "moe_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "rms_norm_eps", "vocab_size", "num_nextn_predict_layers",
            "mtp_loss_weight")
    return {k: getattr(cfg, k) for k in same}


def build(seed=0, **kw):
    """A tiny model whose norms and selection bias are not at their initial
    ones and zeros."""
    paddle.seed(seed)
    cfg = glm4_moe_lite_tiny(**kw)
    model = Glm4MoeLiteForCausalLM(cfg)
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


def test_twenty_heads_on_a_hidden_size_they_do_not_divide(tiny):
    model, cfg = tiny[:2]
    assert cfg.hidden_size % cfg.num_attention_heads
    blocks = list(model.model.layers) + [model.mtp.block]
    assert [type(b.mlp).__name__ for b in blocks] == [
        "Dots3NoteMLP", "DroplessMoE", "DroplessMoE", "DroplessMoE"]
    # ONE body with dots3-note's two kinds: the same class, a third kind
    for b in blocks:
        sa = b.self_attn
        assert type(sa) is dots3_note.LatentAttention and sa.kind == CAUSAL
        assert not hasattr(sa, "gate_proj") and not hasattr(sa, "indexer")
        assert sa.q_b_proj.shape == [16, 20 * (6 + 4)]
        assert sa.o_proj.shape == [20 * 8, 48]
    assert model.mtp.eh_proj.shape == [96, 48]


def test_logits_against_the_reference(tiny):
    model, _, cj, ids, state = tiny
    model.eval()
    got = paddle.jit.to_static(model)(paddle.to_tensor(ids)).data
    model.train()
    want = _compiled.reference(ref.logits, state, ids, cj, HELD)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.fixture(scope="module")
def grads(tiny):
    """The program's losses and gradients of the whole loss, of the loss
    with the module's weight 0 (L_main) and of L_MTP alone (a compiled
    program each), and the reference's three."""
    model, cfg, cj, ids, state = tiny
    out = {"total": _compiled.loss_and_grads(model, model.loss, ids, ids)}
    out["kept"] = (float(model.main_loss.data), float(model.mtp_loss.data))
    cfg.mtp_loss_weight = 0.0
    try:
        out["main"] = _compiled.loss_and_grads(model, model.loss, ids, ids)
    finally:
        cfg.mtp_loss_weight = 0.3
    out["mtp"] = _compiled.loss_and_grads(
        model, lambda i, l: model.losses(i, l)[1], ids, ids)
    want = _compiled.reference(ref.loss_and_grads, state, ids, cj, HELD,
                               "all")
    out.update({"want_" + k: v for k, v in want.items()})
    return out


def test_both_losses_against_the_reference(grads):
    main, extra = grads["main"][0], grads["mtp"][0]
    assert main == pytest.approx(float(grads["want_main"][0]), rel=1e-6)
    assert extra == pytest.approx(float(grads["want_mtp"][0]), rel=1e-6)
    assert grads["total"][0] == pytest.approx(main + 0.3 * extra, rel=1e-6)
    assert grads["total"][0] == pytest.approx(float(grads["want_total"][0]),
                                              rel=1e-6)
    # the step keeps the two beside the one it returns
    assert grads["kept"] == pytest.approx((main, extra), rel=1e-6)
    assert abs(main - extra) > 1e-3


LEAVES = ["embed_tokens", "lm_head", "model.norm.weight",
          "input_layernorm.weight", "post_attention_layernorm.weight",
          "self_attn.q_a_proj", "self_attn.q_a_layernorm.weight",
          "self_attn.q_b_proj", "self_attn.kv_a_proj",
          "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj",
          "self_attn.o_proj", "mlp.gate_up_proj", "mlp.down_proj",
          "mlp.router", "mlp.experts_gate_up", "mlp.experts_down",
          "mlp.shared_gate_up", "mlp.shared_down", "mtp.enorm.weight",
          "mtp.hnorm.weight", "mtp.eh_proj", "mtp.norm.weight"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_against_the_reference(grads, leaf):
    """jax.grad of the reference's whole loss, the module's leaves and the
    trunk's alike."""
    got, want = grads["total"][1], grads["want_total"][1]
    names = [k for k in got if k.endswith(leaf)]
    assert names
    for k in names:
        w = np.asarray(want[k])
        assert np.abs(got[k] - w).max() <= 2e-4 * np.abs(w).max(), k


def test_shared_leaves_get_the_sum_of_both_uses_gradients(grads):
    """Emb and W_head are one leaf each with two uses: the whole loss's
    gradient is L_main's plus the weight times L_MTP's, and both parts are
    there; the module's own leaves get L_MTP's alone."""
    total, main, extra = (grads[k][1] for k in ("total", "main", "mtp"))
    for k in ("model.embed_tokens", "lm_head"):
        assert np.abs(main[k]).max() > 1e-6 and np.abs(extra[k]).max() > 1e-6
        np.testing.assert_allclose(total[k], main[k] + 0.3 * extra[k],
                                   atol=1e-7)
        np.testing.assert_allclose(
            extra[k], np.asarray(grads["want_mtp"][1][k]), atol=2e-7)
    for k in total:
        if k.startswith("mtp."):
            assert main[k] is None or not np.abs(main[k]).max(), k
            np.testing.assert_allclose(total[k], 0.3 * extra[k], atol=1e-7)
    # the trunk's leaves see the module through h^L
    k = "model.layers.2.self_attn.o_proj"
    assert np.abs(extra[k]).max() > 1e-7


def test_weight_zero_gives_the_main_losses_gradients_and_none_on_the_module(
        grads):
    """`loss` under `mtp_loss_weight` 0 (the fixture's "main"): the
    reference's L_main and its gradients, and no gradient at all on the
    module's leaves, which the step then does not build."""
    got, want = grads["main"][1], grads["want_main"][1]
    for k, g in got.items():
        if k.startswith("mtp."):
            assert g is None, k
            assert not np.abs(np.asarray(want[k])).max(), k
        else:
            w = np.asarray(want[k])
            assert np.abs(g - w).max() <= 2e-4 * np.abs(w).max(), k


def test_the_modules_labels_are_two_on_and_the_last_position_is_masked(tiny):
    """L_MTP reads token i+1 through the embedding and token i+2 as its
    label: changing t_1 (no row's label two on, no row's next id but row
    0's) moves it through row 0's input alone; the id fed at position
    T-1 is 0 whatever the sequence holds, and its row and row T-2 have no
    label, so the last token's only way in is as row T-3's label."""
    model, cfg, cj, ids, state = tiny
    one = ids[:1].copy()

    @jax.jit
    def rows(seq):
        a = ref.arch(cj)
        x = ref.hidden_states(state, seq, cj, HELD)[0]
        g = ref.module_states(state, x, seq, cj, HELD)
        lg = ref._mm(ref._rms(g, state["mtp.norm.weight"], a.eps),
                     state["lm_head"])
        return jax.nn.logsumexp(lg, -1)[0], lg[0]

    def mtp_rows(seq):
        """The module's per-row log-sum-exp and logits, the reference's."""
        with jax.default_matmul_precision("highest"):
            return tuple(np.asarray(r) for r in rows(jnp.asarray(seq)))

    assert ref.targets(jnp.asarray(one), 2)[0, :3].tolist() == one[
        0, 2:5].tolist()
    assert ref.targets(jnp.asarray(one), 2)[0, -2:].tolist() == [-1, -1]
    assert ref.next_ids(jnp.asarray(one))[0, -1] == 0
    assert ref.next_ids(jnp.asarray(one))[0, :-1].tolist() == one[
        0, 1:].tolist()
    # the program agrees: the loss is the mean over rows 0..T-3 of the
    # reference's per-row terms against t_{i+2}
    lse, lg = mtp_rows(one)
    want = np.mean([lse[i] - lg[i, one[0, i + 2]] for i in range(T - 2)])
    got = _compiled.run(model, lambda i, l: model.losses(i, l)[1], one, one)
    assert float(got) == pytest.approx(want, rel=1e-5)
    # the last token is row T-2's input and row T-3's label: the rows
    # that have a label do not move with it
    other = one.copy()
    other[0, -1] = (other[0, -1] + 1) % cfg.vocab_size
    lg2 = mtp_rows(other)[1]
    np.testing.assert_allclose(lg2[:T - 2], lg[:T - 2], atol=1e-6)
    assert np.abs(lg2[T - 2] - lg[T - 2]).max() > 1e-4


@pytest.mark.parametrize("group", [4, 10, 20, 3])
def test_head_group_sizes_give_one_result(tiny, group):
    """Groups of 4, 5 (the fixture's) and 10 heads, all 20 at once, and a
    size that does not divide them (one group): the same logits."""
    model, _, _, ids, state = tiny
    other, cfg = build(head_group=group)
    assert other.model.layers[0].self_attn._groups() == {
        4: 5, 10: 2, 20: 1, 3: 1}[group]
    for k, t in other.state_dict().items():
        t.data = state[k]
    x = paddle.to_tensor(ids)
    model.eval(), other.eval()
    want = paddle.jit.to_static(model)(x).data
    got = paddle.jit.to_static(other)(x).data
    model.train()
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6


# -- the reference itself ----------------------------------------------------------

def test_the_references_banded_attention_is_plain_causal_attention(
        monkeypatch):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (64, 2, d)), jnp.float32)
               for d in (8, 8, 6))
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(8)
    s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
    want = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    whole = ref._attend(q, k, v)                  # 64 rows: one block
    monkeypatch.setattr(ref, "ROWS", 4)
    monkeypatch.setattr(ref, "BANDS", 4)
    banded = ref._attend(q, k, v)                 # 4 prefixes of 16, 32, ...
    for got in (whole, banded):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_expert_shares_add_up_to_the_uncut_layer():
    """The guide's share test on the reference: the four shares of 16
    experts, each told which four it holds, the shared expert counted
    once, add up to what the layer that holds all sixteen gives; router and
    bias whole in every share."""
    rng = np.random.default_rng(3)
    h, m, n = 24, 8, 16
    a = ref.Arch(hidden=h, eps=1e-5, layers=1, first_dense=0, n=2, dn=4,
                 dr=2, dv=4, rq=4, rkv=4, theta=1e4, m=m, n_routed=n,
                 top_k=4, norm_topk=True, scaling=1.8, mtp_weight=0.0)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.3, s), jnp.float32)
    w = {"router": f(h, n), "e_score_correction_bias": f(n) * 0.2,
         "experts_gate_up": f(n, h, 2 * m), "experts_down": f(n, m, h),
         "shared_gate_up": f(h, 2 * m), "shared_down": f(m, h)}
    x = f(40, h)
    whole, sent = ref._moe(w, x, a, (0, n), None, None)
    assert int(sent.sum()) == 40 * 4
    shared = ref._swiglu(x, w["shared_gate_up"], w["shared_down"], None)
    total, rows = shared, 0
    for e0 in (0, 4, 8, 12):
        part = dict(w, experts_gate_up=w["experts_gate_up"][e0:e0 + 4],
                    experts_down=w["experts_down"][e0:e0 + 4])
        y, sent = ref._moe(part, x, a, (e0, 4), None, None, shared=False)
        with_shared = ref._moe(part, x, a, (e0, 4), None, None)[0]
        np.testing.assert_allclose(with_shared, y + shared, atol=1e-6)
        total, rows = total + y, rows + int(sent.sum())
    assert rows == 40 * 4
    np.testing.assert_allclose(total, whole, atol=2e-6)


def test_vocabulary_slices_add_up_to_the_uncut_losss_parts():
    """A row's cross-entropy over the whole vocabulary is log-sum-exp
    minus the target's logit: the four slices' logits are the uncut head's
    columns, their log-sum-exps combine to the uncut one, and the target's
    logit lies in one slice. A chip that holds a slice computes the loss
    OVER the slice (ids drawn from it), which `head_loss` gives."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (1, 16, 12)), jnp.float32)
    head = jnp.asarray(rng.normal(0, 0.5, (12, 32)), jnp.float32)
    norm = jnp.ones((12,), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, 32, (1, 16)), jnp.int32)
    whole = float(ref.head_loss(norm, head, x, tgt, 1e-5))
    xr = ref._rms(x, norm, 1e-5)[0]
    lse, got = [], jnp.zeros((16,))
    for v0 in (0, 8, 16, 24):
        lg = xr @ head[:, v0:v0 + 8]
        lse.append(jax.nn.logsumexp(lg, -1))
        mine = (tgt[0] >= v0) & (tgt[0] < v0 + 8)
        got = got + jnp.where(mine, jnp.take_along_axis(
            lg, jnp.clip(tgt[0] - v0, 0, 7)[:, None], -1)[:, 0], 0.0)
        inside = jnp.where(mine, tgt[0] - v0, -1)[None]
        if bool(mine.any()):       # the slice's own loss, over its rows
            own = float(ref.head_loss(norm, head[:, v0:v0 + 8], x, inside,
                                      1e-5))
            want = float(jnp.sum(jnp.where(mine, lse[-1] - jnp.take_along_axis(
                lg, jnp.clip(tgt[0] - v0, 0, 7)[:, None], -1)[:, 0], 0.0))
                / jnp.sum(mine))
            assert own == pytest.approx(want, rel=1e-5)
    combined = jax.nn.logsumexp(jnp.stack(lse), 0)
    assert float(jnp.mean(combined - got)) == pytest.approx(whole, rel=1e-5)


def test_model_told_its_share_matches_the_reference_told_the_same():
    model, cfg = build(seed=2, experts_held=2, expert_offset=4)
    ids = ids_of(cfg, 9, 1)
    state = state_of(model)
    cj = dict(config_json(cfg), n_routed_experts=2, expert_offset=4,
              reduced_from={"n_routed_experts": 8})
    assert state["mtp.block.mlp.experts_down"].shape[0] == 2
    assert state["model.layers.1.mlp.router"].shape[1] == 8
    want = _compiled.reference(ref.losses, state, ids, cj, (4, 2))
    got = _compiled.run(model, model.losses, ids, ids)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-6)
