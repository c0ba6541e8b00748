"""Eager dispatch cache (ISSUE 1: cached eager-op dispatch).

Covers: cache-hit reuse (values AND grads vs the uncached path), tracer
bypass under jit/to_static, AMP-dtype key invalidation, LRU eviction, the
kill switch, the one-dispatch Tensor.__iter__, and the closure checker.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.profiler as profiler
from paddle_tpu.autograd import tape


@pytest.fixture(autouse=True)
def _fresh_cache():
    paddle.set_flags({"FLAGS_eager_dispatch_cache": True,
                      "FLAGS_eager_dispatch_cache_size": 1024})
    profiler.clear_eager_dispatch_cache()
    yield
    paddle.set_flags({"FLAGS_eager_dispatch_cache": True,
                      "FLAGS_eager_dispatch_cache_size": 1024})
    profiler.clear_eager_dispatch_cache()


def _loss_and_grad(x_np, use_cache):
    paddle.set_flags({"FLAGS_eager_dispatch_cache": use_cache})
    out = None
    for _ in range(4):  # >2: past the 2-hit promotion, later iters replay
        x = paddle.to_tensor(x_np.copy())
        x.stop_gradient = False
        h = paddle.reshape(x, [x_np.shape[0], -1])
        y = paddle.tanh(h * 2.0)
        z = paddle.transpose(y, [1, 0])
        loss = paddle.concat([z, z], axis=0).sum() + (y * y).mean()
        loss.backward()
        out = (float(loss.numpy()), np.asarray(x.grad.numpy()))
    return out


def test_cache_hit_values_and_grads_match_uncached():
    x_np = np.random.RandomState(0).randn(4, 3, 2).astype(np.float32)
    loss_c, grad_c = _loss_and_grad(x_np, True)
    hits = profiler.eager_dispatch_cache_stats()["hits"]
    assert hits > 0, "warm loop must hit the cache"
    loss_u, grad_u = _loss_and_grad(x_np, False)
    np.testing.assert_allclose(loss_c, loss_u, rtol=1e-6)
    # the cached op is one fused XLA program, the uncached path runs op
    # by op: their tanh may differ by one f32 ulp (6e-8), and where tanh
    # saturates its gradient 2(1 - y^2) is a difference of nearly equal
    # numbers, so that ulp shows as up to ~2.4e-7 ABSOLUTE however small
    # the gradient is. The bound is four ulps of 1.0; the gradients here
    # are >= 5e-4, so a wrong one is still caught by rtol.
    np.testing.assert_allclose(grad_c, grad_u, rtol=1e-6, atol=5e-7)


def test_profiler_exposes_nonzero_hits_after_warm_loop():
    x = paddle.to_tensor(np.ones((3, 3), np.float32))
    for _ in range(5):
        (x * 1.5).sum()
    s = profiler.eager_dispatch_cache_stats()
    assert s["hits"] > 0
    assert s["misses"] > 0
    assert s["size"] >= 1


def test_tracer_inputs_bypass_under_to_static():
    def fn(a):
        return paddle.tanh(a * 3.0).sum()

    static_fn = paddle.jit.to_static(fn)
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 3).astype(np.float32))
    eager = fn(x)
    before = profiler.eager_dispatch_cache_stats()["bypass_tracer"]
    compiled = static_fn(x)
    after = profiler.eager_dispatch_cache_stats()["bypass_tracer"]
    np.testing.assert_allclose(np.asarray(eager.numpy()),
                               np.asarray(compiled.numpy()), rtol=1e-6)
    assert after > before, "traced ops must take the inline (bypass) path"


def test_amp_dtype_change_invalidates_key():
    x = paddle.to_tensor(np.random.RandomState(2).randn(4, 4).astype(np.float32))
    w = paddle.to_tensor(np.random.RandomState(3).randn(4, 4).astype(np.float32))
    for _ in range(3):
        plain = F.linear(x, w)
    assert plain.dtype == np.float32
    with paddle.amp.auto_cast(dtype="bfloat16"):
        for _ in range(3):
            amp_out = F.linear(x, w)
    import jax.numpy as jnp
    assert amp_out.dtype == jnp.bfloat16
    # back out of autocast: the original fp32 entry must still serve
    again = F.linear(x, w)
    assert again.dtype == np.float32
    np.testing.assert_allclose(np.asarray(plain.numpy()),
                               np.asarray(again.numpy()), rtol=1e-6)


def test_lru_bound_evicts_without_breaking_later_calls():
    paddle.set_flags({"FLAGS_eager_dispatch_cache_size": 4})
    x_np = np.random.RandomState(4).randn(6).astype(np.float32)
    # >4 distinct keys (scale factor is a static kwarg), each called twice
    # so every key passes the 2-hit promotion and compiles an entry
    for k in range(8):
        for _ in range(2):
            paddle.scale(paddle.to_tensor(x_np), scale=float(k))
    s = profiler.eager_dispatch_cache_stats()
    assert s["evictions"] > 0
    assert s["size"] <= 4
    # evicted keys still compute correctly (re-promoted or inline)
    for k in range(8):
        got = np.asarray(paddle.scale(paddle.to_tensor(x_np),
                                      scale=float(k)).numpy())
        np.testing.assert_allclose(got, x_np * k, rtol=1e-6)


def test_kill_switch_bypasses():
    paddle.set_flags({"FLAGS_eager_dispatch_cache": False})
    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    for _ in range(3):
        y = paddle.tanh(x)
    s = profiler.eager_dispatch_cache_stats()
    assert s["hits"] == 0 and s["size"] == 0
    assert s["bypass_flag"] > 0
    np.testing.assert_allclose(np.asarray(y.numpy()), np.tanh(1.0), rtol=1e-6)


def test_static_scalar_type_distinguished():
    # int 1, float 1.0 and True hash equal — keys must not collide
    x = paddle.to_tensor(np.asarray([3.0], np.float32))
    for _ in range(3):
        yi = x * 2
        yf = x * 2.0
    assert np.asarray(yi.numpy())[0] == pytest.approx(6.0)
    assert np.asarray(yf.numpy())[0] == pytest.approx(6.0)


def test_iter_single_dispatch():
    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(4, 3))
    before = tape.dispatch_cache_stats()
    rows = list(x)
    assert len(rows) == 4
    for i, r in enumerate(rows):
        np.testing.assert_allclose(np.asarray(r.numpy()),
                                   np.arange(3) + 3 * i)
    # grads flow through the shared unbind node
    p = paddle.to_tensor(np.ones((3, 2), np.float32))
    p.stop_gradient = False
    total = None
    for row in p:
        s = row.sum()
        total = s if total is None else total + s
    total.backward()
    np.testing.assert_allclose(np.asarray(p.grad.numpy()), np.ones((3, 2)))


def test_iter_empty_and_0d():
    empty = paddle.to_tensor(np.zeros((0, 5), np.float32))
    assert list(empty) == []
    scalar = paddle.to_tensor(np.float32(1.0))
    with pytest.raises(TypeError):
        iter(scalar).__next__()


def test_optimizer_state_dict_grouped_roundtrip():
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt

    m = nn.Linear(4, 3)
    o = opt.Adam(learning_rate=0.01, parameters=m.parameters())
    x = paddle.to_tensor(np.random.RandomState(5).randn(2, 4).astype(np.float32))
    m(x).sum().backward()
    o.step()
    sd = o.state_dict()
    moment_keys = [k for k in sd if k.endswith(".moment1")]
    assert len(moment_keys) == 2  # weight + bias
    o2 = opt.Adam(learning_rate=0.01, parameters=m.parameters())
    o2.set_state_dict(sd)
    assert o2._step_count == o._step_count
    assert len(o2._state) == len(o._state)
    for k, v in o._state.items():
        np.testing.assert_allclose(np.asarray(o2._state[k]), np.asarray(v))


def test_nan_inf_warn_only_single_sync(recwarn):
    paddle.set_flags({"FLAGS_check_nan_inf": True,
                      "FLAGS_check_nan_inf_warn_only": True})
    try:
        x = paddle.to_tensor(np.array([[-1.0, 2.0]], np.float32))
        y = paddle.log(x)  # log(-1) = nan -> warn, not raise
        assert any(issubclass(w.category, RuntimeWarning) for w in recwarn.list)
        assert np.isnan(np.asarray(y.numpy())).any()
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False,
                          "FLAGS_check_nan_inf_warn_only": False})


def test_no_cache_defeating_closures_in_refactored_modules():
    """CI guard: apply_op(lambda ...capturing locals...) must not regrow."""
    import importlib.util
    import pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "check_apply_op_closures",
        root / "tools" / "check_apply_op_closures.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0, "cache-defeating apply_op closures found"
