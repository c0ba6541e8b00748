"""`models/pieces.py`: each shared piece once against a plain `jax.numpy`
statement of it, at one tiny shape under one `jit`. What the pieces add up
to is the model files' own tests' (`test_solar_open2.py`,
`test_granite_hybrid.py`, `test_dots3_note.py`, `test_glm4_moe_lite.py`);
the expert layer and the blocked rule inside `head_loss` are
`test_dropless_moe.py`'s."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import pieces
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.tensor import Tensor

EPS = 1e-5
F32 = jnp.float32


def normal(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(0, scale, shape),
                       F32)


def plain_rms(a, w):
    return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + EPS) * w


def names_in(fn, *args):
    """The lowered text of fn with the scope names in it."""
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_rms_embed_and_head_under_their_scopes():
    a, w = normal(0, 2, 6, 8), 1.0 + normal(1, 8, scale=0.1)
    table, ids = normal(2, 12, 8), jnp.asarray([[3, 0, 11], [5, 5, 1]])
    np.testing.assert_allclose(jax.jit(pieces.rms, static_argnums=2)(
        a, w, EPS), plain_rms(a, w), atol=1e-6)
    np.testing.assert_allclose(jax.jit(pieces.embed)(ids, table), table[ids])
    scaled = jax.jit(lambda i, t: pieces.embed(i, t, 12.0))(ids, table)
    np.testing.assert_allclose(scaled, table[ids] * 12.0, rtol=1e-6)
    np.testing.assert_allclose(jax.jit(pieces.head)(a, table.T),
                               a @ table.T, atol=1e-5)
    assert "norm" in names_in(lambda a_, w_: pieces.rms(a_, w_, EPS), a, w)
    assert "head" in names_in(pieces.head, a, table.T)
    assert "mtp/embed" in names_in(
        lambda i, t: pieces.embed(i, t, under="mtp/embed"), ids, table)


@pytest.mark.parametrize("by", [1, 2])
def test_shifted_labels(by):
    labels = np.arange(10).reshape(2, 5)
    want = np.full((2, 5), -100)
    want[:, :5 - by] = labels[:, by:]
    got = pieces.shifted(paddle.to_tensor(labels), by)
    assert got.tolist() == want.reshape(-1).tolist()
    assert pieces.shifted(labels).tolist() == pieces.shifted(
        labels, 1).tolist()


HEAD_LOSS_FORMS = {     # the four callers' forms
    "untied": dict(),                                          # Solar, dots3
    "tied-and-scaled": dict(tied=True, logit_scale=1 / 8.0),   # Granite
    "scopes-named": dict(scopes=("head", "loss")),             # GLM's trunk
    "second-pass": dict(scopes=("mtp/head", "mtp/loss")),      # GLM's module
}


@pytest.mark.parametrize("form", sorted(HEAD_LOSS_FORMS))
def test_head_loss_against_materialised_logits(form):
    """Value and both gradients, rows that have no label among them, the
    rows not a multiple of the block."""
    how = HEAD_LOSS_FORMS[form]
    x, norm_w = normal(0, 2, 7, 8), 1.0 + normal(1, 8, scale=0.1)
    w = normal(2, 8, 12, scale=0.5)
    w = w.T if how.get("tied") else w
    labels = pieces.shifted(np.random.default_rng(3).integers(
        0, 12, (2, 7)), 2 if form == "second-pass" else 1)

    def plain(x_, norm_w_, w_):
        xn = plain_rms(x_, norm_w_).reshape(-1, 8)
        logits = xn @ (w_.T if how.get("tied") else w_)
        logits = logits * how.get("logit_scale", 1.0)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[:, None], -1)[:, 0]
        seen = labels != -100
        return jnp.sum(jnp.where(seen, jax.nn.logsumexp(logits, -1) - picked,
                                 0.0)) / jnp.sum(seen)

    def blocked(x_, norm_w_, w_):
        return pieces.head_loss(x_, norm_w_, w_, labels, eps=EPS,
                                block_rows=4, **how)

    got = jax.jit(jax.value_and_grad(blocked, (0, 1, 2)))(x, norm_w, w)
    want = jax.jit(jax.value_and_grad(plain, (0, 1, 2)))(x, norm_w, w)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for g, wg in zip(got[1], want[1]):
        np.testing.assert_allclose(g, wg, atol=2e-6)
    text = names_in(blocked, x, norm_w, w)
    assert all(name in text for name in how.get("scopes", ("head", "loss")))


def test_group_of_and_sum_of_groups():
    w = normal(0, 5, 3 * 4 * 2)             # 3 parts, 4 groups, 2 columns
    for g in range(4):
        want = w.reshape(5, 3, 4, 2)[:, :, g].reshape(5, 6)
        np.testing.assert_allclose(pieces.group_of(w, 3, 4, jnp.int32(g)),
                                   want)
    x, ws = normal(1, 2, 6, 8), (normal(2, 4, 8, 8),)

    def group(g, x_, w_):
        return jnp.tanh(x_ @ w_[g]).astype(F32)

    got = jax.jit(lambda x_, w_: pieces.sum_of_groups(group, 4, x_, (w_,)))(
        x, *ws)
    np.testing.assert_allclose(got, sum(group(g, x, *ws) for g in range(4)),
                               atol=1e-6)


def moe_config(**kw):
    base = dict(hidden_size=16, moe_intermediate_size=8, n_routed_experts=8,
                num_experts_per_tok=2, experts_held=4, expert_offset=2,
                n_shared_experts=1, norm_topk_prob=True,
                routed_scaling_factor=1.5, moe_rows=None, dtype="float32")
    base.update(kw)
    return SimpleNamespace(**base)


def test_dropless_moe_of_reads_the_configurations_expert_fields():
    paddle.seed(0)
    mlp = pieces.dropless_moe_of(moe_config(), selection_bias=True)
    assert (mlp.num_experts, mlp.top_k, mlp.first_expert) == (8, 2, 2)
    assert mlp.experts_gate_up.shape == [4, 16, 16]
    assert mlp.router.shape == [16, 8]
    assert mlp.routed_scaling_factor == 1.5 and mlp.norm_topk_prob
    assert mlp.e_score_correction_bias is not None
    assert pieces.dropless_moe_of(
        moe_config()).e_score_correction_bias is None


def test_expert_half_layers_three_outputs_and_the_counters_it_writes():
    """h + experts(RMSNorm(h)) with the layer's two counts beside it; the
    taped form writes them to the layer's buffers, `dropped_pairs` a
    running sum, and `moe_counters` reads them back."""
    paddle.seed(1)
    mlp = pieces.dropless_moe_of(moe_config(moe_rows=8))
    h, ln_w = normal(0, 1, 24, 16), 1.0 + normal(1, 16, scale=0.1)
    ws = [t.data for t in mlp.weights()]
    y, counts, dropped = jax.jit(
        lambda *a: pieces.expert_half(mlp, EPS, *a))(h, ln_w, *ws)
    inner, want_counts, want_dropped = jax.jit(
        lambda a, *ws_: mlp.compute(plain_rms(a, ln_w), *ws_))(h, *ws)
    np.testing.assert_allclose(y, h + inner, atol=1e-6)
    assert counts.tolist() == want_counts.tolist() and counts.shape == (4,)
    assert int(dropped) == int(want_dropped) > 0         # 8 rows are few

    @jax.jit
    def taped(h_, pairs_so_far):
        mlp.dropped_pairs.data = pairs_so_far
        out = pieces.moe_half(mlp, Tensor(h_), Tensor(ln_w), EPS)
        return out.data, mlp.expert_tokens.data, mlp.dropped_pairs.data

    pairs = mlp.dropped_pairs.data
    for n in (1, 2):
        out, tokens, pairs = taped(h, pairs)
        mlp.expert_tokens.data, mlp.dropped_pairs.data = tokens, pairs
        np.testing.assert_allclose(out, y, atol=1e-6)
        got = pieces.moe_counters(
            [SimpleNamespace(mlp=mlp), SimpleNamespace(mlp=None)],
            extra=[mlp.dropped_pairs])
        assert got["expert_tokens"].tolist() == [counts.tolist()]
        assert got["dropped_pairs"].tolist() == [n * int(dropped)]
        assert got["extra"].tolist() == [n * int(dropped)]


@pytest.mark.parametrize("r", [None, 0.22])
def test_swiglu_half_layer(r):
    """Both callers' forms: the plain residual sum, and Granite's `add`,
    the branch times a multiplier; the taped operation carries the name it
    was given."""
    from paddle_tpu.models.granite_hybrid import GraniteMLP
    paddle.seed(2)
    cfg = SimpleNamespace(hidden_size=16, intermediate_size=24,
                          rms_norm_eps=EPS, dtype="float32",
                          residual_multiplier=r)
    half = (pieces.SwiGLUHalf(cfg, "granite_mlp") if r is None
            else GraniteMLP(cfg))
    h, ln_w = normal(0, 2, 5, 16), 1.0 + normal(1, 16, scale=0.1)
    wgu, wd = half.gate_up_proj.data, half.down_proj.data
    a = plain_rms(h, ln_w)
    want = h + (1.0 if r is None else r) * (
        (jax.nn.silu(a @ wgu[:, :24]) * (a @ wgu[:, 24:])) @ wd)
    np.testing.assert_allclose(jax.jit(half.block)(h, ln_w, wgu, wd), want,
                               atol=1e-6)
    out = half(paddle.to_tensor(np.asarray(h)), Tensor(ln_w))
    assert out._node.name == "granite_mlp"
    np.testing.assert_allclose(out.data, want, atol=1e-6)
    assert sorted(half.state_dict()) == ["down_proj", "gate_up_proj"]


class _Doubling(Layer):
    """A toy layer: x -> 2x; the first returns it bare, the second beside
    a loss of its own, the third beside None."""

    def __init__(self, cfg, index):
        super().__init__()
        self.index = index

    def forward(self, x):
        y = x * 2.0
        return y if self.index == 0 else (
            y, y.sum() if self.index == 1 else None)


def test_decoder_stack_embeds_runs_the_layers_and_norms_last():
    paddle.seed(3)
    cfg = SimpleNamespace(vocab_size=12, hidden_size=8, num_hidden_layers=3,
                          rms_norm_eps=EPS, dtype="float32")
    stack = pieces.DecoderStack(cfg, _Doubling, embedding_multiplier=3.0)
    assert list(stack.state_dict()) == ["embed_tokens", "norm.weight"]
    ids = np.asarray([[1, 7, 7, 0]])
    rows = stack.embed_tokens.data[ids] * 3.0 * 8.0
    aux = []
    np.testing.assert_allclose(
        stack(paddle.to_tensor(ids), final_norm=False, aux=aux).data, rows,
        rtol=1e-6)
    assert len(aux) == 1                       # the one layer that has one
    np.testing.assert_allclose(aux[0].data, rows.sum() / 2.0, rtol=1e-5)
    np.testing.assert_allclose(
        stack(paddle.to_tensor(ids)).data,
        plain_rms(rows, stack.norm.weight.data), atol=1e-5)


# -- the half-layers on the shared residual piece (ISSUE 48) --------------------

def test_the_moved_half_layers_give_x_plus_their_branch_to_the_bit():
    """The accepted models' half-layers are written against `pieces.PLAIN`:
    each gives, bit for bit, its input plus the branch it hands the path
    (the expert half, the dense half and its Granite form with the
    multiplier inside one rounding)."""
    from paddle_tpu.models.granite_hybrid import GraniteMLP
    paddle.seed(4)
    mlp = pieces.dropless_moe_of(moe_config(moe_rows=64))
    h, ln_w = normal(0, 1, 24, 16), 1.0 + normal(1, 16, scale=0.1)
    ws = [t.data for t in mlp.weights()]
    y, counts, dropped = jax.jit(
        lambda *a: pieces.expert_half(mlp, EPS, *a))(h, ln_w, *ws)
    inner, want_counts, _ = jax.jit(
        lambda a, *ws_: mlp.compute(pieces.rms(a, ln_w, EPS), *ws_))(h, *ws)
    np.testing.assert_array_equal(y, h + inner)
    assert counts.tolist() == want_counts.tolist() and int(dropped) == 0
    for r in (None, 0.22):
        cfg = SimpleNamespace(hidden_size=16, intermediate_size=24,
                              rms_norm_eps=EPS, dtype="float32",
                              residual_multiplier=r)
        half = (pieces.SwiGLUHalf(cfg, "mlp") if r is None
                else GraniteMLP(cfg))
        x = normal(2, 2, 5, 16)
        wgu, wd = half.gate_up_proj.data, half.down_proj.data
        got = jax.jit(half.block)(x, ln_w, wgu, wd)
        branch = jax.jit(half.branch)(x, ln_w, wgu, wd)
        want = x + branch if r is None else pieces.branch(x, branch, r)
        np.testing.assert_array_equal(got, want)
        assert (half.join is None) == (r is None)


@pytest.mark.parametrize("batch", [1, 3])
def test_latent_attentions_block_is_x_plus_its_branch_on_the_plain_path(
        batch):
    """`LatentAttention.block` on `pieces.PLAIN` (what dots3-note and
    GLM-4.7-Flash run): each sequence's x + `_sequence`(x) bit for bit, one
    sequence (the cells' batch) and three (`jax.lax.map`); its other outputs
    handed through."""
    from paddle_tpu.models.dots3_note import CAUSAL, LatentAttention
    from paddle_tpu.models.glm4_moe_lite import glm4_moe_lite_tiny
    paddle.seed(5)
    layer = LatentAttention(glm4_moe_lite_tiny(), CAUSAL)
    x = normal(3, batch, 16, 48)
    ws = [1.0 + normal(4, 48, scale=0.1)] + [t.data for t in (
        layer.q_a_proj, layer.q_a_layernorm.weight, layer.q_b_proj,
        layer.kv_a_proj, layer.kv_a_layernorm.weight, layer.kv_b_proj,
        layer.o_proj)]
    y, li, pairs = jax.jit(layer.block)(x, *ws)
    mixed = jnp.stack([jax.jit(layer._sequence)(x[b], *ws)[0]
                       for b in range(batch)])
    np.testing.assert_array_equal(y, x + mixed)
    assert float(li) == 0.0 and int(pairs) == 0
    assert float(jnp.max(jnp.abs(mixed))) > 1e-3

    # the helper both forms map a one-sequence branch with
    f = pieces.over_sequences(lambda xs: (xs * 2.0, jnp.sum(xs)),
                              lambda s: (jnp.max(s),))
    doubled, top = jax.jit(f)(x)
    np.testing.assert_array_equal(doubled, x * 2.0)
    assert float(top) == pytest.approx(
        max(float(jnp.sum(x[b])) for b in range(batch)), rel=1e-5)


def test_decoder_stack_expands_and_sums_the_streams_it_is_told_of():
    paddle.seed(6)
    cfg = SimpleNamespace(vocab_size=12, hidden_size=8, num_hidden_layers=2,
                          rms_norm_eps=EPS, dtype="float32")
    plain = pieces.DecoderStack(cfg, _Doubling)
    wide = pieces.DecoderStack(cfg, _Doubling, streams=4)
    wide.embed_tokens.data = plain.embed_tokens.data
    ids = paddle.to_tensor(np.asarray([[1, 7, 7, 0]]))
    assert plain.streams == 1
    np.testing.assert_allclose(
        wide(ids, final_norm=False).data,
        4.0 * plain(ids, final_norm=False).data, rtol=1e-6)
    assert wide.layers[0].index == 0
    text = names_in(lambda i: wide(Tensor(i), final_norm=False).data,
                    ids.data)
    assert "hc/expand" in text and "hc/reduce" in text
