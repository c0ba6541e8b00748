"""models/solar_open2.py against its plain reference
(`tests/reference/solar_open2.py`) on seeded weights at tiny widths: each
kind of layer and the whole model (logits, loss, every gradient leaf),
the counters the compiled step writes and the names it carries. Program
and reference each run under one `jit` (`tests/_compiled.py`). The
operator is `tests/test_gated_delta_rule.py`'s, the expert layer and the
blocked head + loss `tests/test_dropless_moe.py`'s."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _compiled  # noqa: E402
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
    cj = cfg_json(c)
    held = ref.held_of(cj)
    # the reference under jit: a program a call, not one an operation
    want = jax.jit(lambda s, i: ref.logits(s, i, cj, held))(state, ids)
    np.testing.assert_allclose(np.asarray(_compiled.run(m, m, ids)),
                               np.asarray(want),
                               atol=tol * float(jnp.max(jnp.abs(want))))
    loss, grads = _compiled.loss_and_grads(m, m.loss, ids, ids)
    want_loss, want_g = jax.jit(
        lambda s, i: ref.loss_and_grads(s, i, cj, held))(state, ids)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert grads and set(grads) <= set(want_g)
    for name, got in grads.items():
        assert got is not None, name
        g = np.asarray(want_g[name])
        np.testing.assert_allclose(
            got, g, atol=tol * max(np.abs(g).max(), 1e-6), err_msg=name)
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


# -- the delta-rule layer alone: its own backward, its scans, its event --------

KDA_LEAVES = ("qkv_proj", "conv_weight", "decay_down", "decay_up", "A_log",
              "dt_bias", "beta_proj", "gate_down", "gate_up", "o_norm.weight",
              "o_proj")


def _kda_layer(**kw):
    """(layer, its configuration, x [2, 37, H], [the norm's weight, the 11
    leaves] in `block`'s order, all seeded)."""
    from paddle_tpu.models.solar_open2 import KDAttention
    paddle.seed(11)
    c = solar_open2_tiny(num_hidden_layers=1, gqa_layers=(), **kw)
    layer = KDAttention(c)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    leaves = [p.data for p in layer.weights()]        # KDA_LEAVES' order
    # a norm weight and an o_norm weight that are not all ones
    ln_w = 1.0 + 0.1 * jax.random.normal(keys[0], (c.hidden_size,))
    leaves[9] = 1.0 + 0.1 * jax.random.normal(keys[1], leaves[9].shape)
    x = jax.random.normal(keys[2], (2, 37, c.hidden_size))
    return layer, c, x, [ln_w] + leaves


@pytest.mark.parametrize("head_group, groups", [(4, 1), (2, 2), (3, 1)],
                         ids=["one-group", "two-groups", "heads-not-divided"])
def test_kda_block_and_every_gradient_match_the_reference(head_group, groups):
    """`KDAttention.block` (norm and shared low-rank product once, the
    groups' scan, the stacked output projection) and the cotangent of x
    and of each of the 12 weights through its hand-driven backward,
    against autodiff of the reference's half layer; 4 heads in one group,
    in two, and in one because 3 does not divide them."""
    layer, c, x, ws = _kda_layer(kda_head_group=head_group)
    assert layer._groups() == groups
    a = ref.arch(cfg_json(c))
    target = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def mine(x, *ws):
        y = layer.block(x, *ws)
        return jnp.mean((y - target) ** 2), y

    def plain(x, ln_w, *leaves):
        w = {"ln1": ln_w}
        w.update({"mixer." + k: v for k, v in zip(KDA_LEAVES, leaves)})
        y = ref.mixer_half(w, x, "kda", a)
        return jnp.mean((y - target) ** 2), y

    every = tuple(range(len(ws) + 1))
    (loss, y), got = jax.jit(jax.value_and_grad(
        mine, argnums=every, has_aux=True))(x, *ws)
    with jax.default_matmul_precision("highest"):
        (want_loss, want_y), want = jax.jit(jax.value_and_grad(
            plain, argnums=every, has_aux=True))(x, *ws)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name, g, w in zip(("x", "ln1") + KDA_LEAVES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        top = float(jnp.abs(w).max())
        assert top > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5 * top, err_msg=name)


def _walk(jaxpr, inside=(), found=None):
    """[(eqn, the scans it lies in)] of a jaxpr and everything under it,
    kernel bodies apart."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append((eqn, inside))
        if eqn.primitive.name == "pallas_call":
            continue
        under = inside + (eqn,) if eqn.primitive.name == "scan" else inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _walk(sub, under, found)
    return found


def test_the_group_scans_hold_one_hidden_width_product_a_pass(monkeypatch):
    """The jaxpr of the block's gradient, with what nothing reads taken
    out (`jax.vjp` of the checkpointed group traces a forward whose output
    the backward never uses): two scans over the groups. The forward's
    holds ONE product with a `hidden_size` side (q|k|v) and no norm; the
    backward's recomputed forward one, its transposed operations that
    product's two and the output projection's two; the delta-rule
    forward kernel is there twice (forward, recomputed), not three times."""
    from jax._src.interpreters import partial_eval as pe
    from paddle_tpu.kernels import gated_delta_rule as gdr
    monkeypatch.setattr(gdr, "_on_tpu", lambda: True)
    layer, c, _, ws = _kda_layer(linear_head_dim=128, kda_chunk=64)
    H = c.hidden_size
    x = jnp.zeros((1, 128, H))

    def loss(x, *ws):
        return jnp.sum(layer.block(x, *ws) ** 2)

    traced = jax.make_jaxpr(jax.grad(
        loss, argnums=tuple(range(len(ws) + 1))))(x, *ws).jaxpr
    live, _ = pe.dce_jaxpr(traced, [True] * len(traced.outvars))
    eqns = _walk(live)
    scans = [e for e, _ in eqns if e.primitive.name == "scan"]
    assert len(scans) == 2 and all(
        e.params["length"] == layer._groups() for e in scans)

    def hidden_products(scan, recomputed=None):
        out = []
        for e, inside in eqns:
            if e.primitive.name != "dot_general" or scan not in inside:
                continue
            if H not in sum((tuple(v.aval.shape) for v in e.invars), ()):
                continue
            marked = "rematted_computation" in str(e.source_info.name_stack)
            if recomputed is None or marked == recomputed:
                out.append(e)
        return out

    forward, backward = scans
    assert len(hidden_products(forward)) == 1
    assert len(hidden_products(backward, recomputed=True)) == 1
    assert len(hidden_products(backward, recomputed=False)) == 4
    in_scans = [e for e, inside in eqns if inside]
    assert not [e for e in in_scans if "rms_norm" in str(e.params.get(
        "name", "")) or "rms_norm" in str(e.source_info.name_stack)]
    kernels = [e.params["name"] for e, _ in eqns
               if e.primitive.name == "pallas_call"]
    assert kernels.count("kda_chunk_states") == 2, kernels
    assert kernels.count("kda_chunk_states_bwd") == 1, kernels
    # the norm and the shared product: once in the forward, once recomputed
    shared = (H, 2 * c.kda_low_rank + c.linear_num_heads)
    outside = [e for e, inside in eqns if not inside
               and e.primitive.name == "dot_general"
               and e.invars[1].aval.shape == shared
               and e.params["dimension_numbers"][0] == ((2,), (0,))]
    assert len(outside) == 2


def test_a_traced_kda_layer_leaves_one_kda_groups_event():
    import paddle_tpu.optimizer as popt
    from paddle_tpu.observability import scopes, spans
    c = solar_open2_tiny(num_hidden_layers=1, gqa_layers=())
    paddle.seed(0)
    m = SolarOpen2ForCausalLM(c)
    opt = popt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt, lambda i, l: m.loss(i, l))
    x = paddle.to_tensor(_ids(c, 2, 32))
    step._build()
    spans.clear()
    step._compiled.trace(*step._call_args((x, x)))
    events = [ev["attrs"] for ev in spans.ring() if ev["name"] == "kda.groups"]
    assert events == [{
        "groups": "2", "heads_per_group": "2",
        "hidden_width_products_in_group": "1",
        "shared_columns": str(2 * c.kda_low_rank + c.linear_num_heads),
        "stacked_out_bytes": str(2 * 32 * 4 * 8 * 4)}]
    assert "kda.groups" in scopes.SETUP
