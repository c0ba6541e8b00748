"""models/solar_open2.py against its plain reference
(`tests/reference/solar_open2.py`) on seeded weights at tiny widths: each
kind of layer and the whole model (logits, loss, every gradient leaf),
the chunked delta-rule operator against the token-by-token recurrence,
the expert layer's shares against the uncut layer, droplessness under a
skewed router, the counters the compiled step writes, the blocked head +
loss, and the names the compiled step carries."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu.kernels import gated_delta_rule as gdr  # noqa: E402
from paddle_tpu.kernels.gated_delta_rule import chunk_gated_delta_rule  # noqa: E402
from paddle_tpu.kernels.grouped_matmul import grouped_matmul  # noqa: E402
from paddle_tpu.models import (SolarOpen2Config, SolarOpen2ForCausalLM,  # noqa: E402
                               solar_open2_tiny)
from paddle_tpu.nn.layer.moe import dropless_moe  # noqa: E402
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
    want = ref.logits(state, jnp.asarray(ids), cj, ref.held_of(cj))
    np.testing.assert_allclose(np.asarray(m(x).data), np.asarray(want),
                               atol=tol * float(jnp.max(jnp.abs(want))))
    loss = m.loss(x, x)
    loss.backward()
    want_loss, want_g = ref.loss_and_grads(state, jnp.asarray(ids), cj,
                                           ref.held_of(cj))
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


# -- the chunked operator -----------------------------------------------------

def _qkvgb(T, H=3, dk=16, dv=16, strong=False, batch=None, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    lead = (T,) if batch is None else (batch, T)
    q = ref._l2norm(jax.random.normal(ks[0], lead + (H, dk)))
    k = ref._l2norm(jax.random.normal(ks[1], lead + (H, dk)))
    v = jax.random.normal(ks[2], lead + (H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], lead + (H, dk), minval=-6,
                                    maxval=3.0 if strong else 0.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], lead + (H,)))
    return q, k, v, g, beta


@pytest.mark.parametrize("T,chunk,strong", [
    (37, 16, False), (64, 32, False), (150, 32, True), (96, 64, False)])
def test_chunked_delta_rule_matches_the_recurrence(T, chunk, strong):
    """Forward and backward at lengths the chunk does not divide, and
    under a decay down to exp(-20) a token: exp(G_i - G_j) split
    carelessly overflows there."""
    args = _qkvgb(T, strong=strong)

    def chunked(*a):
        return chunk_gated_delta_rule(*(x[None] for x in a), chunk=chunk)[0]

    want = ref.delta_rule_recurrence(*args)
    got = chunked(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2, 3, 4))(*args)

    for a, b in zip(grads(chunked), grads(ref.delta_rule_recurrence)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * float(jnp.abs(b).max()))


def _through_the_kernels(*a, chunk):
    """The chip's route (one Pallas kernel a pass), here through the
    interpreter."""
    return gdr._fused(*a, chunk, 1.0 / np.sqrt(a[0].shape[-1]), True)


def _cast(args, dtype):
    return tuple(x.astype(dtype) for x in args[:3]) + tuple(args[3:])


def _out_and_grads(fn, args):
    return (fn(*args),) + jax.grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))),
        argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_chunk_state_kernels_walk_the_chunks_as_the_scan_does(dtype, tol):
    """The kernel route against the `jax.numpy` route on the same inputs,
    at the head width the kernels tile (128): the output and the
    cotangent of every operand, in the operands' own dtypes."""
    args = _cast(_qkvgb(70, H=2, dk=128, dv=128, batch=1, seed=1), dtype)

    for got, want in zip(
            _out_and_grads(functools.partial(_through_the_kernels, chunk=16),
                           args),
            _out_and_grads(functools.partial(chunk_gated_delta_rule, chunk=16),
                           args)):
        assert got.shape == want.shape and got.dtype == want.dtype
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=tol * np.abs(want).max())


@pytest.mark.parametrize("T,chunk,strong,dtype,tol", [
    (70, 16, False, jnp.float32, 2e-5),
    (150, 64, True, jnp.float32, 5e-5),
    (100, 64, False, jnp.bfloat16, 1e-2),
    (40, 16, True, jnp.bfloat16, 1e-2)])
def test_chunked_delta_rule_through_the_kernels_matches_the_recurrence(
        T, chunk, strong, dtype, tol):
    """The fused forward and backward kernels against the token-by-token
    recurrence, output and all five gradients: a length the chunk does
    not divide, the weak and the strong decay (down to exp(-20) a token),
    float32 and bf16 operands, a chunk of one sub-block and of four."""
    args = _qkvgb(T, H=2, dk=128, dv=128, strong=strong, seed=2)

    def chunked(*a):
        return _through_the_kernels(
            *(x[None] for x in _cast(a, dtype)), chunk=chunk)[0]

    got = _out_and_grads(chunked, args)
    assert got[0].dtype == dtype and bool(jnp.isfinite(got[0]).all())
    want = _out_and_grads(ref.delta_rule_recurrence, args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol * float(jnp.abs(b).max()))


def test_gated_delta_rule_is_public_and_batched():
    q, k, v, g, beta = _qkvgb(40, batch=2, seed=4)
    o = F.gated_delta_rule(*(paddle.to_tensor(np.asarray(t))
                             for t in (q, k, v, g, beta)), chunk=16)
    for b in range(2):
        want = ref.delta_rule_recurrence(q[b], k[b], v[b], g[b], beta[b])
        np.testing.assert_allclose(np.asarray(o.data[b]), np.asarray(want),
                                   atol=1e-5)


# -- the layers and the model -------------------------------------------------

@pytest.mark.parametrize("kind", ["gqa", "kda"])
def test_one_layer_of_each_kind_matches_the_reference(kind):
    c = solar_open2_tiny(num_hidden_layers=1,
                         gqa_layers=(0,) if kind == "gqa" else ())
    m = _against_reference(c)
    names = {k.split(".")[3] for k in m.state_dict() if "layers.0" in k}
    assert ("self_attn" in names) == (kind == "gqa")
    assert ("linear_attn" in names) == (kind == "kda")


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


# -- the expert layer ---------------------------------------------------------

def _moe_weights(E, H=32, M=16, seed=0, skew=None):
    ks = jax.random.split(jax.random.key(seed), 6)
    w = {"router": jax.random.normal(ks[0], (H, E)) * 0.5,
         "experts_gate_up": jax.random.normal(ks[1], (E, H, 2 * M)) * 0.1,
         "experts_down": jax.random.normal(ks[2], (E, M, H)) * 0.1,
         "shared_gate_up": jax.random.normal(ks[3], (H, 2 * M)) * 0.1,
         "shared_down": jax.random.normal(ks[4], (M, H)) * 0.1}
    if skew is not None:          # every token scores expert `skew` highest
        w["router"] = w["router"].at[:, skew].set(0.0)
        w["router"] = w["router"] * 0.01
    x = jax.random.normal(ks[5], (64, H))
    if skew is not None:
        x = x.at[:, 0].set(30.0)
        w["router"] = w["router"].at[0, skew].set(1.0)
    return w, x


def _arch(E, k):
    return ref.Arch(hidden=32, nh=1, kvh=1, d=1, nl=1, dl=1, rank=1, taps=1,
                    m=16, n_routed=E, top_k=k, norm_topk=True, scaling=1.0,
                    eps=1e-5, gqa_layers=())


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their routed parts, plus the
    shared expert counted once, are the uncut reference layer."""
    E, k = 8, 3
    w, x = _moe_weights(E)
    whole, sent = ref._moe(w, x, _arch(E, k), (0, E), None, None)
    total = ref._swiglu(x, w["shared_gate_up"], w["shared_down"], None)
    rows = []
    for e0 in range(0, E, 2):
        y, counts, dropped = dropless_moe(
            x, w["router"], w["experts_gate_up"][e0:e0 + 2],
            w["experts_down"][e0:e0 + 2], first_expert=e0, top_k=k)
        assert int(dropped) == 0
        total = total + y
        rows += counts.tolist()
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    assert rows == sent.tolist() and sum(rows) == 64 * k


def test_dropless_under_a_router_that_sends_most_pairs_to_one_expert():
    E, k = 8, 2
    w, x = _moe_weights(E, skew=5)
    a = _arch(E, k)
    args = (x, w["router"], w["experts_gate_up"][4:6], w["experts_down"][4:6])
    want, sent = ref._moe(w | {
        "experts_gate_up": w["experts_gate_up"][4:6],
        "experts_down": w["experts_down"][4:6]}, x, a, (4, 2), None, None)
    want = want - ref._swiglu(x, w["shared_gate_up"], w["shared_down"], None)
    y, counts, dropped = dropless_moe(*args, first_expert=4, top_k=k)
    assert counts.tolist() == sent.tolist() and counts[1] == 64   # all of them
    assert int(dropped) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    # a buffer smaller than what came: the rest is counted, not lost unseen
    _, counts, dropped = dropless_moe(*args, first_expert=4, top_k=k, rows=40)
    assert counts.tolist() == sent.tolist()
    assert int(dropped) == int(counts.sum()) - 40 > 0


def test_grouped_matmul_is_a_matmul_a_group():
    x = jax.random.normal(jax.random.key(0), (24, 8))
    w = jax.random.normal(jax.random.key(1), (3, 8, 5))
    sizes = jnp.asarray([5, 0, 11], jnp.int32)
    got = grouped_matmul(x, w, sizes)
    np.testing.assert_allclose(np.asarray(got[:5]), np.asarray(x[:5] @ w[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[5:16]),
                               np.asarray(x[5:16] @ w[2]), atol=1e-5)


def test_expert_layer_is_public_and_says_what_it_cannot_hold():
    with pytest.raises(ValueError, match="not among"):
        paddle.nn.DroplessMoE(8, 4, num_experts=8, top_k=2, experts_held=4,
                              first_expert=6)
    layer = paddle.nn.DroplessMoE(32, 16, num_experts=8, top_k=2,
                                  experts_held=2, first_expert=2)
    y = layer(paddle.to_tensor(np.ones((2, 5, 32), np.float32)))
    assert y.shape == [2, 5, 32]
    assert layer.expert_tokens.shape == [2]


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


# -- the blocked head + loss --------------------------------------------------

@pytest.mark.parametrize("rows,block", [(37, 8), (64, 16), (5, 2048)])
def test_linear_cross_entropy_matches_the_materialised_logits(rows, block):
    rng = np.random.default_rng(0)
    h = paddle.to_tensor(rng.normal(size=(rows, 16)).astype(np.float32))
    w = paddle.to_tensor(rng.normal(size=(16, 50)).astype(np.float32))
    h.stop_gradient = w.stop_gradient = False
    labels = rng.integers(0, 50, (rows,)).astype(np.int32)
    labels[::5] = -100
    got = F.linear_cross_entropy(h, w, paddle.to_tensor(labels),
                                 block_rows=block)
    got.backward()
    gh, gw = np.asarray(h.grad.data), np.asarray(w.grad.data)

    def dense(h_, w_):
        lg = h_ @ w_
        keep = labels != -100
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, jnp.where(keep, labels, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(keep, nll, 0.0)) / keep.sum()

    want, (wh, ww) = jax.value_and_grad(dense, argnums=(0, 1))(
        h.data, w.data)
    assert float(got.data) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(gh, np.asarray(wh), atol=1e-6)
    np.testing.assert_allclose(gw, np.asarray(ww), atol=1e-6)
