"""What the attention kernel's backward needs from its forward is kept
across the backward by `TrainStep`'s default remat policy (ISSUE 32): the
splash wrapper stamps (out, logsumexp) with `SPLASH_RESIDUALS`, the policy
keeps that name, and every body that holds attention under a
`jax.checkpoint` runs the forward kernel once a step, not twice.

Trace-only on the CPU (`flash_attention._on_tpu` patched, the gradient's
jaxpr read; no kernel is lowered), plus one numeric case through the
Pallas interpreter: a policy moves memory, never values."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _compiled
import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.framework import core
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.models.granite_hybrid import (GraniteHybridForCausalLM,
                                              granite_hybrid_tiny)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.solar_open2 import (KDAttention,
                                           SolarOpen2ForCausalLM,
                                           solar_open2_tiny)
from paddle_tpu.observability import scopes, spans

SEQ = 128


@pytest.fixture
def fake_tpu(monkeypatch):
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)


def _dense():
    """Two decoder layers through `llama._scan_stack`."""
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=256, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=SEQ, dtype="float32"))


def _granite():
    """`GraniteAttention.block` under its own checkpoint."""
    return GraniteHybridForCausalLM(granite_hybrid_tiny(
        num_hidden_layers=1, layer_types=("attention",), hidden_size=256,
        num_attention_heads=4, num_key_value_heads=2))


def _solar():
    """`GatedAttention.block`: a scan over its two KV heads' groups."""
    return SolarOpen2ForCausalLM(solar_open2_tiny(
        num_hidden_layers=1, gqa_layers=(0,), head_dim=64))


# body -> (model, query heads ONE call holds)
BODIES = {"dense-scan-stack": (_dense, 4),
          "granite-attention": (_granite, 4),
          "solar-gated-attention-groups": (_solar, 2)}


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


def _traced_step(model, remat_policy, event="train_step.kept"):
    """(names of the Pallas calls in the step's jaxpr, the `event`s its
    trace left in the ring)."""
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l),
                                remat_policy=remat_policy)
    x = paddle.to_tensor(np.zeros((1, SEQ), np.int32))
    step._build()
    spans.clear()
    jaxpr = step._compiled.trace(*step._call_args((x, x))).jaxpr
    found = [ev["attrs"] for ev in spans.ring() if ev["name"] == event]
    return _pallas_calls(jaxpr.jaxpr, []), found


@pytest.mark.parametrize("body", sorted(BODIES))
@pytest.mark.parametrize("remat_policy, forwards",
                         [("save_matmul_outputs", 1), (None, 2)],
                         ids=["default-policy", "policy-none"])
def test_the_forward_kernel_is_in_the_step_once(fake_tpu, body,
                                                remat_policy, forwards):
    """One forward call a body in the jaxpr (a scan turns it once a layer
    or a group) under the default policy; without a policy the backward
    holds a second one. The backward is there once either way."""
    paddle.seed(0)
    calls, kept = _traced_step(BODIES[body][0](), remat_policy)
    assert calls.count("splash_mqa_fwd_residuals") == forwards, calls
    assert calls.count("splash_mqa_dkv_no_residuals") == 1, calls
    assert len(kept) == (1 if forwards == 1 else 0)


@pytest.mark.parametrize("body", sorted(BODIES) + ["dots3-window-layers"])
def test_the_dq_kernel_follows_the_routed_form(fake_tpu, body):
    """`train_step.splash_backward` says the form a call site's backward
    took, and the kernels in the step are that form's: a dense-causal call
    makes dq, dk and dv in the dkv kernel (ISSUE 50: no
    `splash_mqa_dq_no_residuals`), a call under a window keeps the dq
    kernel beside it (dots3-note's three window layers; its full layer's
    core is the `dsa_*` kernels)."""
    paddle.seed(0)
    if body in BODIES:
        model, sites, form = BODIES[body][0](), 1, "one_kernel"
    else:
        from paddle_tpu.models.dots3_note import (Dots3NoteForCausalLM,
                                                  dots3_note_tiny)
        model = _compiled.shapes_only(lambda: Dots3NoteForCausalLM(
            dots3_note_tiny(index_n_heads=8, swa_qk_nope_head_dim=60,
                            swa_v_head_dim=64, v_head_dim=64,
                            qk_nope_head_dim=60)))
        sites, form = 3, "two_kernels"
    calls, said = _traced_step(model, "save_matmul_outputs",
                               "train_step.splash_backward")
    assert [ev["form"] for ev in said] == [form] * sites, said
    assert calls.count("splash_mqa_dkv_no_residuals") == sites, calls
    assert calls.count("splash_mqa_dq_no_residuals") == (
        sites if form == "two_kernels" else 0), calls
    for ev in said:
        assert sorted(ev) == ["block_kv_dkv", "form", "kv_heads_a_call",
                              "partial_bytes", "partials"]
        assert ev["partials"] == ("1" if form == "one_kernel" else "0")


@pytest.mark.parametrize("body", sorted(BODIES))
def test_train_step_kept_says_the_name_and_the_bytes(fake_tpu, body):
    """One `train_step.kept` event a stamped call: out [1, heads, SEQ, 64]
    float32 and logsumexp [1, heads, SEQ] float32 of ONE call (the scan
    over Solar's groups stacks two of them)."""
    make, heads = BODIES[body]
    paddle.seed(0)
    _, kept = _traced_step(make(), "save_matmul_outputs")
    assert kept == [{"kept": fa.SPLASH_RESIDUALS,
                     "bytes": str(heads * SEQ * (64 * 4 + 4))}]
    assert "train_step.kept" in scopes.SETUP


def test_remat_keeps_asks_the_armed_policy(fake_tpu):
    """`core.remat_keeps` asks the armed policy itself, as jax.checkpoint
    does: no policy and "nothing" keep nothing, the default keeps its
    names and no other, a callable that keeps every name keeps this one.
    Outside a train step (serving prefill) nothing is armed: no event."""
    resolve = paddle.jit.resolve_remat_policy
    every_name = jax.checkpoint_policies.save_any_names_but_these()
    for policy, keeps in ((None, False), ("nothing", False),
                          ("save_matmul_outputs", True), (every_name, True)):
        with core.remat_policy_guard(resolve(policy)):
            assert core.remat_keeps(fa.SPLASH_RESIDUALS) is keeps
            assert core.remat_keeps("a_name_of_nobody") is (
                policy is every_name)
    q = jnp.zeros((1, SEQ, 4, 64))
    spans.clear()
    jax.make_jaxpr(lambda a: fa.flash_attention_bshd(
        a, a[:, :, :2], a[:, :, :2], causal=True))(q)
    assert not [ev for ev in spans.ring() if ev["name"] == "train_step.kept"]


def test_the_delta_rule_groups_program_is_the_parents():
    """A delta-rule group stamps no name and, since ISSUE 37, the block's
    own backward checkpoints its groups without a policy (one that kept a
    value would keep the forward `jax.vjp` traces there alive: a third
    forward), so the program it lowers to under the default policy is the
    one it lowers to under none."""
    paddle.seed(0)
    cfg = solar_open2_tiny()
    layer = KDAttention(cfg)
    ws = [jnp.ones((cfg.hidden_size,), jnp.float32)] + [
        p.data for p in layer.weights()]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, cfg.hidden_size))

    def lowered(policy):
        with core.remat_policy_guard(
                paddle.jit.resolve_remat_policy(policy)):
            grad = jax.grad(lambda a, *w: layer.block(a, *w).sum(),
                            argnums=tuple(range(len(ws) + 1)))
            return jax.jit(grad).lower(x, *ws).as_text()

    assert lowered("save_matmul_outputs") == lowered(None)


def test_policies_move_memory_never_values():
    """GQA 4 / 2 x 64, S = 256, the kernels through the Pallas
    interpreter: loss and every gradient of a checkpointed attention
    block are bit for bit the same with the residuals kept (one forward
    kernel in the program) and under "nothing" (two)."""
    B, S, Hq, Hk, D, H = 1, 256, 4, 2, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (B, S, H))
    wqkv = jax.random.normal(ks[1], (H, (Hq + 2 * Hk) * D)) * 0.05
    wo = jax.random.normal(ks[2], (Hq * D, H)) * 0.05
    target = jax.random.normal(ks[3], (B, S, H))

    def block(x, wqkv, wo):
        qkv = x @ wqkv
        q = qkv[..., :Hq * D].reshape(B, S, Hq, D)
        k = qkv[..., Hq * D:(Hq + Hk) * D].reshape(B, S, Hk, D)
        v = qkv[..., (Hq + Hk) * D:].reshape(B, S, Hk, D)
        o = fa.flash_attention_bshd(q, k, v, causal=True, interpret=True)
        return x + o.reshape(B, S, Hq * D) @ wo

    def run(policy):
        with core.remat_policy_guard(
                paddle.jit.resolve_remat_policy(policy)):
            def loss(x, wqkv, wo):
                y = jax.checkpoint(
                    block, policy=core.current_remat_policy())(x, wqkv, wo)
                return ((y - target) ** 2).mean()

            vg = jax.value_and_grad(loss, argnums=(0, 1, 2))
            calls = _pallas_calls(jax.make_jaxpr(vg)(x, wqkv, wo).jaxpr, [])
            return (calls.count("splash_mqa_fwd_residuals"),
                    jax.tree_util.tree_leaves(jax.jit(vg)(x, wqkv, wo)))

    n_kept, kept = run("save_matmul_outputs")
    n_again, again = run("nothing")
    assert (n_kept, n_again) == (1, 2)
    assert float(kept[0]) > 0 and all(
        float(jnp.abs(g).max()) > 0 for g in kept[1:])
    for a, b in zip(kept, again):
        assert np.array_equal(np.asarray(a), np.asarray(b))
