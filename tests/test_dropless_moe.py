"""The expert layer and the blocked head + loss that models/solar_open2.py
is built from, each against a plain statement of the same sum:
`nn.DroplessMoE` / `dropless_moe` (its shares against the uncut reference
layer of `tests/reference/solar_open2.py`, droplessness under a skewed
router), the layer's row moves (`kernels/row_moves.py`: both routes against
`jnp.take` and `.at[].add`, the kernel in the Pallas interpreter; the layer
against the form it had before, `tests/_moe_parent_rows.py`),
`kernels.grouped_matmul`, and `F.linear_cross_entropy` against the
materialised logits (its gradients made in the forward: the products
counted in its jaxpr and through the four models that call it)."""
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
from paddle_tpu.kernels import row_moves as rm  # noqa: E402
from paddle_tpu.kernels.grouped_matmul import grouped_matmul  # noqa: E402
from paddle_tpu.nn.layer.moe import dropless_moe  # noqa: E402
from reference import solar_open2 as ref  # noqa: E402
import _compiled  # noqa: E402
import _moe_parent_rows as parent  # noqa: E402


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


# -- the row moves -----------------------------------------------------------

ROWS, TOKENS, WIDTH, TOP_K = 256, 512, 128, 8     # shapes the kernel takes


@pytest.fixture
def kernel_route(monkeypatch):
    """The chip's route on the CPU: the scatter-add kernel in the Pallas
    interpreter, at tiles small enough that a case walks several."""
    fused = rm._scatter_add_fused
    monkeypatch.setattr(rm, "_on_tpu", lambda: True)
    monkeypatch.setattr(rm, "_scatter_add_fused", functools.partial(
        fused, interpret=True, tile=64))


def _rows_case(case):
    """(src [ROWS, WIDTH] bf16 with NaN in its dead rows, idx, live)."""
    rng = np.random.default_rng(3)
    idx = rng.integers(0, TOKENS, ROWS).astype(np.int32)
    live = {"top_k_rows_on_one_token": 200, "tokens_with_none": 60,
            "nothing_live": 0, "all_live": ROWS, "one_live": 1,
            "a_chunk_and_one": 129}[case]
    if case == "top_k_rows_on_one_token":
        idx[40:40 + TOP_K] = 77               # and its rows lie side by side
        idx[150] = 77                         # one more, far from them
    if case == "tokens_with_none":
        idx[:live] = 448 + idx[:live] % 64    # the first seven tiles are empty
    src = rng.standard_normal((ROWS, WIDTH)).astype(np.float32)
    src[live:] = np.nan
    idx[live:] = rng.integers(-5, 2 * TOKENS, ROWS - live)   # never looked at
    return (jnp.asarray(src, jnp.bfloat16), jnp.asarray(idx),
            jnp.asarray(live, jnp.int32))


def _plain_scatter_add(src, idx, live):
    out = np.zeros((TOKENS, WIDTH), np.float64)
    np.add.at(out, np.asarray(idx)[:live],
              np.asarray(src, np.float32)[:live].astype(np.float64))
    return out


ROW_CASES = ["top_k_rows_on_one_token", "tokens_with_none", "nothing_live",
             "all_live", "one_live", "a_chunk_and_one"]


@pytest.mark.parametrize("case", ROW_CASES)
@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_scatter_add_rows_is_the_float32_sum_of_the_live_rows(
        case, route, request):
    if route == "kernel":
        request.getfixturevalue("kernel_route")
    assert rm.route(ROWS, TOKENS, WIDTH, jnp.bfloat16) == route
    src, idx, live = _rows_case(case)
    got = np.asarray(rm.scatter_add_rows(src, idx, live, TOKENS), np.float32)
    want = _plain_scatter_add(src, idx, int(live))
    # float32 accumulation, one rounding: the rounded float64 sum
    np.testing.assert_array_equal(
        got, np.asarray(jnp.asarray(want, jnp.bfloat16), np.float32))
    assert not np.any(got[np.setdiff1d(np.arange(TOKENS),
                                       np.asarray(idx)[:int(live)])])


@pytest.mark.parametrize("case", ROW_CASES)
def test_gather_rows_is_take_over_the_live_rows(case):
    src, idx, live = _rows_case(case)
    table = jax.random.normal(jax.random.key(2), (TOKENS, WIDTH), jnp.bfloat16)
    got = np.asarray(rm.gather_rows(table, idx, live), np.float32)
    n = int(live)
    np.testing.assert_array_equal(
        got[:n], np.asarray(table, np.float32)[np.asarray(idx)[:n]])
    assert not np.any(got[n:])


@pytest.mark.parametrize("case", ["top_k_rows_on_one_token", "nothing_live"])
@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_each_row_move_is_the_others_transpose(case, route, request):
    if route == "kernel":
        request.getfixturevalue("kernel_route")
    src, idx, live = _rows_case(case)
    table = jax.random.normal(jax.random.key(2), (TOKENS, WIDTH), jnp.bfloat16)
    g_rows = jnp.nan_to_num(src) + 1          # a cotangent of the gather
    _, pull = jax.vjp(lambda t: rm.gather_rows(t, idx, live), table)
    np.testing.assert_array_equal(
        np.asarray(pull(g_rows)[0], np.float32),
        np.asarray(rm.scatter_add_rows(g_rows, idx, live, TOKENS),
                   np.float32))
    _, pull = jax.vjp(lambda s: rm.scatter_add_rows(s, idx, live, TOKENS),
                      jnp.nan_to_num(src))
    np.testing.assert_array_equal(
        np.asarray(pull(table)[0], np.float32),
        np.asarray(rm.gather_rows(table, idx, live), np.float32))


def test_the_kernels_walk_is_tiles_plus_chunks_and_skips_dead_chunks():
    """`_visits` over a buffer whose first 150 places are live: every tile
    once at least, a tile's visits side by side, no chunk past the live
    ones, steps behind the last visit flagged as nothing."""
    key = np.full(512, 1024, np.int32)
    key[:150] = np.sort(np.random.default_rng(0).integers(0, 1024, 150))
    t, c, f = map(np.asarray, rm._visits(jnp.asarray(key), 150, 1024, 256,
                                         128))
    assert len(t) == 1024 // 256 + 512 // 128
    assert sorted(set(t.tolist())) == [0, 1, 2, 3] and (np.diff(t) >= 0).all()
    assert c.max() <= 149 // 128
    used = f != 0
    assert [int((f[t == i] & 2 != 0).sum()) for i in range(4)] == [1] * 4
    assert [int((f[t == i] & 4 != 0).sum()) for i in range(4)] == [1] * 4
    for i in range(4):                        # a tile's rows lie in its chunks
        mine = np.nonzero((key >= 256 * i) & (key < 256 * (i + 1)))[0]
        assert set(mine // 128) == set(c[(t == i) & (f & 1 != 0)].tolist())
    assert not used[np.nonzero(used)[0][-1] + 1:].any()


def _bf16_layer(E=8, held=4, k=4, seed=0):
    w, x = _moe_weights(E, H=WIDTH, M=128, seed=seed)
    x = jnp.tile(x, (4, 1)) + 0.1 * jax.random.normal(
        jax.random.key(seed + 9), (256, WIDTH))
    bf = lambda a: a.astype(jnp.bfloat16)
    return (bf(x), bf(w["router"]), bf(w["experts_gate_up"][:held]),
            bf(w["experts_down"][:held])), dict(first_expert=0, top_k=k)


@pytest.mark.parametrize("rows", [None, 2048, 128],
                         ids=["rows_are_pairs", "rows_over_pairs",
                              "buffer_overflows"])
@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_layer_and_gradients_match_the_form_before(rows, route, request):
    """`dropless_moe` in bf16 against the parent's gather / scatter-add
    form: the output and the gradients of x, the router and both expert
    weights within bf16 rounding, the counters to the digit; with a buffer
    that overflows the dropped pairs are counted and add nothing."""
    if route == "kernel":
        request.getfixturevalue("kernel_route")
    args, kw = _bf16_layer()
    g = jax.random.normal(jax.random.key(5), args[0].shape, jnp.bfloat16)

    def loss(f):
        def run(*a):
            y, counts, dropped = f(*a, rows=rows, **kw)
            return jnp.sum(y.astype(jnp.float32) * g), (y, counts, dropped)
        return jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2, 3),
                                          has_aux=True))
    (_, (y, counts, dropped)), grads = loss(dropless_moe)(*args)
    (_, (y0, counts0, dropped0)), grads0 = loss(parent.dropless_moe)(*args)
    assert counts.tolist() == counts0.tolist()
    assert int(dropped) == int(dropped0)
    assert (int(dropped) > 0) == (rows == 128)
    for got, want in zip((y,) + grads, (y0,) + grads0):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        np.testing.assert_allclose(got, want, rtol=2 ** -6,
                                   atol=2 ** -7 * np.abs(want).max())


def test_a_tokens_float32_sum_is_no_further_from_float64_than_bf16_adds():
    """Eight bf16 rows a token: summed in float32 and rounded once they
    lie no further from the float64 sum than added one at a time in bf16,
    token by token, and nearer in all."""
    rng = np.random.default_rng(1)
    idx = jnp.asarray(np.repeat(np.arange(ROWS // TOP_K), TOP_K), jnp.int32)
    src = jnp.asarray(rng.standard_normal((ROWS, WIDTH)), jnp.bfloat16)
    exact = _plain_scatter_add(src, idx, ROWS)
    new = np.asarray(rm.scatter_add_rows(src, idx, jnp.int32(ROWS), TOKENS),
                     np.float64)
    old = np.asarray(jnp.zeros((TOKENS, WIDTH), jnp.bfloat16).at[idx].add(src),
                     np.float64)
    assert (np.abs(new - exact) <= np.abs(old - exact) + 1e-12).all()
    assert np.abs(new - exact).sum() < 0.5 * np.abs(old - exact).sum()


def _primitives(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append((eqn.primitive.name,
                      tuple(getattr(v.aval, "shape", ()) for v in eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def test_the_kernel_route_holds_no_scatter_add_of_rows(monkeypatch):
    """On the chip's route the layer's forward holds no scatter-add at all,
    and its backward none that writes [tokens, hidden]: the four row moves
    are two gathers and two calls of the kernel (the router's
    `take_along_axis` keeps its own small transpose)."""
    monkeypatch.setattr(rm, "_on_tpu", lambda: True)
    args, kw = _bf16_layer()
    T, H = args[0].shape
    fwd = _primitives(jax.make_jaxpr(
        lambda *a: dropless_moe(*a, **kw)[0])(*args).jaxpr)
    assert not [p for p in fwd if p[0] == "scatter-add"]
    assert [p[0] for p in fwd].count("pallas_call") == 1
    both = _primitives(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(dropless_moe(*a, **kw)[0].astype(jnp.float32)),
        argnums=(0, 1, 2, 3)))(*args).jaxpr)
    assert not [p for p in both if p[0] == "scatter-add" and (T, H) in p[1]]
    assert [p[0] for p in both].count("pallas_call") == 2
    monkeypatch.setattr(rm, "_on_tpu", lambda: False)
    plain = _primitives(jax.make_jaxpr(
        lambda *a: dropless_moe(*a, **kw)[0])(*args).jaxpr)
    assert [p for p in plain if p[0] == "scatter-add"]


@pytest.mark.parametrize("on_tpu,dtype,route", [
    (True, "bfloat16", "kernel"), (True, "float32", "xla"),
    (False, "bfloat16", "xla")])
def test_a_traced_layer_leaves_one_moe_rows_event(monkeypatch, on_tpu, dtype,
                                                  route):
    from paddle_tpu.observability import scopes, spans
    monkeypatch.setattr(rm, "_on_tpu", lambda: on_tpu)
    args, kw = _bf16_layer()
    args = tuple(a.astype(dtype) for a in args)
    spans.clear()
    jax.make_jaxpr(lambda *a: dropless_moe(*a, **kw)[0])(*args)
    events = [ev["attrs"] for ev in spans.ring() if ev["name"] == "moe.rows"]
    assert events == [{
        "route": route, "rows": "1024", "hidden": str(WIDTH),
        "row_bytes": str(WIDTH * jnp.dtype(dtype).itemsize), "tokens": "256",
        "tile": str(rm.TILE), "chunk": str(rm.CHUNK)}]
    assert "moe.rows" in scopes.SETUP


# -- the blocked head + loss --------------------------------------------------

def _dense_ce(h, w, labels, tied=False, logit_scale=None):
    lg = (h @ (w.T if tied else w)).astype(jnp.float32)
    if logit_scale is not None:
        lg = lg * logit_scale
    keep = labels != -100
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, jnp.where(keep, labels, 0)[:, None], -1)[:, 0]
    return jnp.sum(jnp.where(keep, nll, 0.0)) / jnp.maximum(keep.sum(), 1)


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

    want, (wh, ww) = jax.value_and_grad(
        lambda h_, w_: _dense_ce(h_, w_, labels), argnums=(0, 1))(
            h.data, w.data)
    assert float(got.data) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(gh, np.asarray(wh), atol=1e-6)
    np.testing.assert_allclose(gw, np.asarray(ww), atol=1e-6)


@pytest.mark.parametrize("case", [
    "tied", "logit_scale", "loss_times_3", "loss_used_twice", "all_ignored",
    "rows_the_block_does_not_divide", "frozen_head"])
def test_linear_cross_entropy_gradients_made_in_the_forward(case):
    """Loss, dh and dW against the dense form where the rule's backward has
    something to get wrong: the table as it is stored, the scale, an
    upstream cotangent that is not 1, no row that counts, a padded last
    block, and a head that is not differentiated (no dW, no product for
    it)."""
    from paddle_tpu.nn.functional.loss import _linear_cross_entropy
    rows = 37 if case == "rows_the_block_does_not_divide" else 32
    kw = {"tied": {"tied": True}, "logit_scale": {"logit_scale": 0.25}}.get(
        case, {})
    use = {"loss_times_3": lambda l: l * 3.0,
           "loss_used_twice": lambda l: l * l + l,
           "rows_the_block_does_not_divide": lambda l: l * 3.0}.get(
               case, lambda l: l)
    rng = np.random.default_rng(1)
    h = paddle.to_tensor(rng.normal(size=(rows, 16)).astype(np.float32))
    w = rng.normal(size=(16, 50)).astype(np.float32)
    w = paddle.to_tensor(w.T.copy() if case == "tied" else w)
    h.stop_gradient = False
    w.stop_gradient = case == "frozen_head"
    labels = rng.integers(0, 50, (rows,)).astype(np.int32)
    labels[::5] = -100
    if case == "all_ignored":
        labels[:] = -100
    got = use(F.linear_cross_entropy(h, w, paddle.to_tensor(labels),
                                     block_rows=8, **kw))
    got.backward()
    want, (wh, ww) = jax.value_and_grad(
        lambda h_, w_: use(_dense_ce(h_, w_, labels, **kw)),
        argnums=(0, 1))(h.data, w.data)
    assert float(got.data) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(np.asarray(h.grad.data), np.asarray(wh),
                               atol=1e-6)
    if case == "all_ignored":
        assert float(got.data) == 0.0
        assert not np.asarray(h.grad.data).any()
        assert not np.asarray(w.grad.data).any()
    if case != "frozen_head":
        np.testing.assert_allclose(np.asarray(w.grad.data), np.asarray(ww),
                                   atol=1e-6)
        return
    assert w.grad is None
    made = _primitives(jax.make_jaxpr(jax.grad(
        lambda h_: _linear_cross_entropy(h_, w.data, labels, 8, -100)))(
            h.data).jaxpr)
    assert [p[0] for p in made].count("dot_general") == 2
    assert not [p for p in made if w.data.shape in p[1]]


def _table_products(jaxpr, vocab):
    """The `dot_general`s with a side `vocab` long (a block's logits, or
    what is made from their gradient), each with the chain of equations
    that hold it."""
    found = []

    def walk(jp, inside):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general" and any(
                    vocab in getattr(v.aval, "shape", ())
                    for v in (*eqn.invars, *eqn.outvars)):
                found.append(inside)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inside + (eqn.primitive.name,))

    walk(jaxpr, ())
    return found


def test_the_head_and_loss_multiply_by_the_table_three_times_a_block():
    """One set of logits a block: the gradient's jaxpr holds the forward
    product and the two made from the logits' gradient, all three in the
    ONE scan of the forward rule (the form before made the logits again in
    a second, the backward's: four products), and the backward holds no
    product at all; an evaluation that is not differentiated holds one."""
    from paddle_tpu.nn.functional.loss import _linear_cross_entropy
    h, w = jnp.ones((64, 32), jnp.bfloat16), jnp.ones((32, 200), jnp.bfloat16)
    labels = jnp.arange(64, dtype=jnp.int32)

    def f(h_, w_):
        return _linear_cross_entropy(h_, w_, labels, 16, -100)

    grad = jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(h, w).jaxpr
    assert _table_products(grad, 200) == [("scan",)] * 3
    names = [p[0] for p in _primitives(grad)]
    assert names.count("scan") == 1 and names.count("dot_general") == 3
    plain = _primitives(jax.make_jaxpr(f)(h, w).jaxpr)
    assert [p[0] for p in plain].count("dot_general") == 1


def _tiny(name):
    from paddle_tpu.models import (dots3_note, glm4_moe_lite, granite_hybrid,
                                   solar_open2)
    make = {"granite": (granite_hybrid.GraniteHybridForCausalLM,
                        granite_hybrid.granite_hybrid_tiny),
            "solar": (solar_open2.SolarOpen2ForCausalLM,
                      solar_open2.solar_open2_tiny),
            "dots3": (dots3_note.Dots3NoteForCausalLM,
                      dots3_note.dots3_note_tiny),
            "glm": (glm4_moe_lite.Glm4MoeLiteForCausalLM,
                    glm4_moe_lite.glm4_moe_lite_tiny)}[name]
    kw = {"layer_types": ("mamba",)} if name == "granite" else {}
    cfg = make[1](vocab_size=200, num_hidden_layers=1, **kw)
    return _compiled.shapes_only(lambda: make[0](cfg))


@pytest.mark.parametrize("name,passes", [
    ("granite", 1), ("solar", 1), ("dots3", 1), ("glm", 2)])
def test_a_models_step_multiplies_by_the_table_three_times_a_pass(name,
                                                                  passes):
    """The whole step of each model that calls the blocked head + loss, at
    its smallest config, one layer deep, with a vocabulary no other width
    equals: three products a pass over the head, none under a
    `jax.checkpoint` (a call site that wraps the rule in one runs its
    gradient-making forward twice); and what the tape counts as kept for
    the backward under `head_loss` is the two gradients and at most the
    pass's input, never the norm's output."""
    import paddle_tpu.optimizer as popt
    from paddle_tpu.observability import spans
    model = _tiny(name)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    x = paddle.to_tensor(np.zeros((1, 32), np.int32))
    step._build()
    spans.clear()
    jaxpr = step._compiled.trace(*step._call_args((x, x))).jaxpr.jaxpr
    products = _table_products(jaxpr, 200)
    assert len(products) == 3 * passes
    assert not [p for p in products if "checkpoint" in p or "remat2" in p]
    kept = {ev["attrs"]["scope"]: int(ev["attrs"]["bytes"])
            for ev in spans.ring() if ev["name"] == "train_step.residuals"}
    hidden = model.cfg.hidden_size
    dw, dx = 200 * hidden * 4, 32 * hidden * 4
    assert kept["head_loss"] in (dw + dx, dw + dx + dx)
    if passes == 2:
        assert kept["mtp_head_loss"] in (dw + dx, dw + dx + dx)
