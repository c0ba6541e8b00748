"""models/granite_hybrid.py against its plain reference
(`chipbench/reference_granitemoehybrid.py`: float32, "highest", the
state-space recurrence token by token) on seeded weights at tiny widths:
the whole model in the published pattern (logits, loss, every gradient
leaf), each of the four multipliers and the convolution's bias, the tied
table's gradient, the chip's kernels under the model, and the names the
compiled step carries. The operator is `tests/test_ssd.py`'s, the
convolution `tests/test_short_conv.py`'s."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import _compiled  # noqa: E402
import paddle_tpu as paddle  # noqa: E402
from chipbench import reference_granitemoehybrid as ref  # noqa: E402
from paddle_tpu.models import (GraniteHybridConfig,  # noqa: E402
                               GraniteHybridForCausalLM, granite_hybrid_tiny)

# float32 on both sides at "highest": what is left is the order of sums
# (chunked against token by token, blocks of rows, fused against plain);
# a multiplier left out or applied twice, a wrong mask or a missing bias
# is tenths
TOL = 2e-5


def cfg_json(c: GraniteHybridConfig):
    """The configuration-file keys the reference reads, for a program
    config."""
    return dict(
        hidden_size=c.hidden_size, num_hidden_layers=c.num_hidden_layers,
        num_attention_heads=c.num_attention_heads,
        num_key_value_heads=c.num_key_value_heads,
        mamba_n_heads=c.mamba_n_heads, mamba_d_head=c.mamba_d_head,
        mamba_d_state=c.mamba_d_state, mamba_n_groups=c.mamba_n_groups,
        mamba_d_conv=c.mamba_d_conv, rms_norm_eps=c.rms_norm_eps,
        embedding_multiplier=c.embedding_multiplier,
        attention_multiplier=c.attention_multiplier,
        residual_multiplier=c.residual_multiplier,
        logits_scaling=c.logits_scaling, layer_types=list(c.layer_types),
        vocab_size=c.vocab_size)


def _ids(c, batch, seq, seed=0):
    return np.random.default_rng(seed).integers(
        0, c.vocab_size, (batch, seq)).astype(np.int32)


def _model(c, seed=3):
    """A model whose vectors are not the ones they start as: norm
    weights, D and the bias move the output only where they differ."""
    paddle.seed(seed)
    m = GraniteHybridForCausalLM(c)
    key = jax.random.key(seed + 2)
    for i, (k, t) in enumerate(m.state_dict().items()):
        if t.data.ndim == 1 and not k.endswith(("A_log", "dt_bias")):
            t.data = t.data + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), t.data.shape, t.data.dtype)
    return m, {k: t.data for k, t in m.state_dict().items()}


def _logits(c, batch=2, seq=37):
    """(program's, reference's) logits of a seeded model."""
    m, state = _model(c)
    ids = _ids(c, batch, seq)
    cj = cfg_json(c)
    want = jax.jit(lambda s, i: ref.logits(s, i, cj))(state, ids)
    return np.asarray(_compiled.run(m, m, ids)), np.asarray(want)


@functools.lru_cache(maxsize=None)
def _whole():
    """The published pattern at the tiny preset (m m A m): program and
    reference, logits, loss and gradients, each under one `jit`."""
    c = granite_hybrid_tiny()
    m, state = _model(c)
    ids = _ids(c, 2, 37)
    cj = cfg_json(c)
    logits = np.asarray(_compiled.run(m, m, ids))
    loss, grads = _compiled.loss_and_grads(m, m.loss, ids, ids)
    want_logits = jax.jit(lambda s, i: ref.logits(s, i, cj))(state, ids)
    want_loss, want_g = jax.jit(
        lambda s, i: ref.loss_and_grads(s, i, cj))(state, ids)
    return (logits, loss, grads, np.asarray(want_logits),
            float(want_loss), {k: np.asarray(v) for k, v in want_g.items()})


def test_logits_and_loss_match_the_reference():
    logits, loss, _, want_logits, want_loss, _ = _whole()
    np.testing.assert_allclose(logits, want_logits,
                               atol=TOL * np.abs(want_logits).max())
    assert loss == pytest.approx(want_loss, rel=1e-5)


LEAVES = ("embed_tokens", "input_layernorm.weight",
          "post_attention_layernorm.weight", "mamba.in_proj",
          "mamba.conv_weight", "mamba.conv_bias", "mamba.A_log",
          "mamba.dt_bias", "mamba.D", "mamba.norm.weight", "mamba.out_proj",
          "self_attn.qkv_proj", "self_attn.o_proj",
          "shared_mlp.gate_up_proj", "shared_mlp.down_proj",
          "model.norm.weight")


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(leaf):
    _, _, grads, _, _, want = _whole()
    names = [k for k in grads if k.endswith(leaf)]
    assert names and set(grads) == set(want)
    for name in names:
        g = want[name]
        np.testing.assert_allclose(
            grads[name], g, atol=TOL * max(np.abs(g).max(), 1e-6),
            err_msg=name)


def test_every_leaf_is_of_a_kind_the_cases_above_name():
    _, _, grads, _, _, _ = _whole()
    assert all(k.endswith(LEAVES) for k in grads)
    kinds = ["attention" if "self_attn" in "".join(
        k for k in grads if f"layers.{i}." in k) else "mamba"
        for i in range(4)]
    assert kinds == ["mamba", "mamba", "attention", "mamba"]


# -- the multipliers and the bias ----------------------------------------------

def _small(**kw):
    return granite_hybrid_tiny(num_hidden_layers=2,
                               layer_types=("mamba", "attention"), **kw)


@functools.lru_cache(maxsize=None)
def _small_base():
    return _logits(_small(), 1, 24)[0]


@pytest.mark.parametrize("name,value", [
    ("embedding_multiplier", 5.0), ("attention_multiplier", 50.0),
    ("residual_multiplier", 0.6), ("logits_scaling", 3.0)])
def test_a_multiplier_moves_the_output_as_the_equations_say(name, value):
    """At another value than the preset's the program still reads what
    the reference reads, and not what it read before: a multiplier that
    is left out, or applied twice, fails one of the two."""
    got, want = _logits(_small(**{name: value}), 1, 24)
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max())
    assert np.abs(got - _small_base()).max() > 1e-3 * np.abs(want).max()


def test_logits_scaling_divides_and_nothing_else():
    got = _logits(_small(logits_scaling=4.0), 1, 24)[0]
    np.testing.assert_allclose(got, _small_base() * 2.0, rtol=1e-5,
                               atol=1e-7)


def test_the_convolutions_bias_moves_the_output():
    """The same model with its bias set to zero reads something else, and
    what the reference reads without it."""
    c = _small()
    m, state = _model(c)
    ids = _ids(c, 1, 24)
    name = "model.layers.0.mamba.conv_bias"
    assert float(jnp.abs(state[name]).max()) > 0.1
    m.state_dict()[name].data = jnp.zeros_like(state[name])
    got = np.asarray(_compiled.run(m, m, ids))
    want = np.asarray(jax.jit(lambda s, i: ref.logits(s, i, cfg_json(c)))(
        {**state, name: jnp.zeros_like(state[name])}, ids))
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max())
    assert np.abs(got - _small_base()).max() > 1e-3 * np.abs(want).max()


def test_tied_tables_gradient_is_the_sum_of_its_two_uses():
    """As the embedding and as the head, each taken alone in the
    reference (the other use held fixed): both are there, and the
    program's one gradient is their sum."""
    c = granite_hybrid_tiny()
    _, _, grads, _, _, _ = _whole()
    _, state = _model(c)
    ids, cj, a = jnp.asarray(_ids(c, 2, 37)), cfg_json(c), None
    a = ref.arch(cj)
    f32 = {k: v.astype(jnp.float32) for k, v in state.items()}

    def loss(lookup, head):
        x = ref.hidden_states({**f32, "model.embed_tokens": lookup}, ids, cj)
        return ref.head_loss(f32["model.norm.weight"], head, x, ids, a)

    table = f32["model.embed_tokens"]
    as_lookup, as_head = jax.jit(jax.grad(loss, argnums=(0, 1)))(table, table)
    got = grads["model.embed_tokens"]
    for part in (as_lookup, as_head):
        assert float(jnp.abs(part).max()) > 1e-3 * np.abs(got).max()
    np.testing.assert_allclose(got, np.asarray(as_lookup + as_head),
                               atol=TOL * np.abs(got).max())


# -- the chip's kernels under the model ----------------------------------------

def test_mamba_layer_through_the_kernels_matches_the_reference(monkeypatch):
    """One state-space layer at shapes the kernels tile (8 heads of 64, a
    state of 128, chunks of 128 tokens, x | B | C of whole lane tiles),
    both pairs of kernels in the Pallas interpreter: `MambaMixer` is held
    to the reference through the chip's route too, the sequence padded
    to a chunk."""
    from paddle_tpu.kernels import short_conv as sc
    from paddle_tpu.kernels import ssd
    through, fused, fused_bias = [], ssd._fused, sc._fused_bias

    def core(*a):
        through.append(("ssd", a[0].shape))
        return fused(*a[:7], True)

    def conv(pre, w, b, widths, _):
        through.append(("conv", pre.shape))
        return fused_bias(pre, w, b, widths, True)

    for mod in (ssd, sc):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd, "_fused", core)
    monkeypatch.setattr(sc, "_fused_bias", conv)
    c = granite_hybrid_tiny(
        num_hidden_layers=1, layer_types=("mamba",), mamba_n_heads=8,
        mamba_d_head=64, mamba_d_state=128, mamba_chunk_size=128)
    got, want = _logits(c, 1, 37)
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max())
    assert through == [("conv", (1, 37, 768)), ("ssd", (1, 128, 8, 64))]


# -- the compiled step -------------------------------------------------------

def test_compiled_step_carries_the_scopes_and_trains():
    import paddle_tpu.optimizer as popt
    c = granite_hybrid_tiny()
    m, _ = _model(c, seed=5)
    opt = popt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt, lambda i, l: m.loss(i, l))
    x = paddle.to_tensor(_ids(c, 2, 48))
    text = step.lower(x, x).compile().as_text()
    for name in ("ssm/proj", "ssm/conv", "ssm/dt", "ssm/core", "ssm/norm",
                 "ssm/out", "attn/qkv", "attn/core", "attn/out", "mlp",
                 "norm", "head", "loss"):
        assert f"/{name}/" in text, name
    assert "jvp(embed)" in text        # one operation: jax brackets the name
    assert "rematted_computation" in text and "transpose(" in text
    loss0 = float(step(x, x).data)
    assert float(step(x, x).data) < loss0
    assert step._traces == 1
    # one table: the optimizer holds one pair of moments for it
    tables = [k for k in m.state_dict() if "embed" in k or "lm_head" in k]
    assert tables == ["model.embed_tokens"]


def test_config_holds_the_pattern_and_refuses_groups():
    c = GraniteHybridConfig()
    assert [i for i, k in enumerate(c.layer_types) if k == "attention"] == [
        5, 15, 25, 35]
    assert (c.head_dim, c.mamba_d_inner) == (64, 4096)
    with pytest.raises(ValueError):
        GraniteHybridConfig(mamba_n_groups=8)
    with pytest.raises(ValueError):
        GraniteHybridConfig(num_hidden_layers=3, layer_types=("mamba",))
