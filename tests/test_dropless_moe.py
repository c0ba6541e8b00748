"""The expert layer and the blocked head + loss that models/solar_open2.py
is built from, each against a plain statement of the same sum:
`nn.DroplessMoE` / `dropless_moe` (its shares against the uncut reference
layer of `tests/reference/solar_open2.py`, droplessness under a skewed
router), `kernels.grouped_matmul`, and `F.linear_cross_entropy` against
the materialised logits."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu.kernels.grouped_matmul import grouped_matmul  # noqa: E402
from paddle_tpu.nn.layer.moe import dropless_moe  # noqa: E402
from reference import solar_open2 as ref  # noqa: E402


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
