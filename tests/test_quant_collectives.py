"""Quantized collectives (ISSUE 8, EQuARX arxiv 2506.17615): blockwise
int8/fp8 wire quantization, the two-phase quantized all-reduce chain in
shard_map programs, the TrainStep/ShardingPlan gradient-sync seam with
error feedback, wire-byte telemetry, and the FLAGS_quant_collectives=0
kill switch (bitwise parity with the GSPMD paths)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed.sharding import ShardingPlan
from paddle_tpu.distributed.topology import AxisGroup
from paddle_tpu.quantization import comm as qcomm

N_DEV = 8


def _mesh():
    return Mesh(np.asarray(jax.devices()[:N_DEV]).reshape(N_DEV), ("dp",))


def _group(mesh):
    return AxisGroup(mesh, "dp", N_DEV)


@pytest.fixture(autouse=True)
def _restore_quant_flags():
    yield
    paddle.set_flags({"FLAGS_quant_collectives": 1,
                      "FLAGS_quant_collectives_block": 256})


# -- blockwise quantization plumbing ----------------------------------------

class TestBlockwise:
    def test_int8_roundtrip_error_bounded(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 512).astype(np.float32) *
                        rng.uniform(0.1, 10, (4, 1)).astype(np.float32))
        q, sc = qcomm.quantize_blocks(x, 128, "int8")
        assert q.dtype == jnp.int8 and sc.shape == (4, 4)
        back = qcomm.dequantize_blocks(q, sc, 128)
        # per-block error <= half a quantization step = absmax / 254
        err = np.abs(np.asarray(back - x)).reshape(4, 4, 128).max(-1)
        bound = np.abs(np.asarray(x)).reshape(4, 4, 128).max(-1) / 254 + 1e-7
        assert (err <= bound).all()

    def test_zero_blocks_exact(self):
        x = jnp.zeros((256,), jnp.float32)
        q, sc = qcomm.quantize_blocks(x, 64, "int8")
        assert np.asarray(qcomm.dequantize_blocks(q, sc, 64)).max() == 0.0

    @pytest.mark.skipif(not qcomm.supports_fp8(), reason="no fp8 on jax")
    def test_fp8_roundtrip(self):
        x = jnp.asarray(np.random.RandomState(1).randn(512).astype(
            np.float32))
        q, sc = qcomm.quantize_blocks(x, 256, "fp8")
        assert q.dtype == jnp.float8_e4m3fn
        back = np.asarray(qcomm.dequantize_blocks(q, sc, 256))
        # e4m3: 3 mantissa bits -> <= ~6.25% relative error per element
        assert np.abs(back - np.asarray(x)).max() <= \
            0.07 * np.abs(np.asarray(x)).max()

    def test_shard_sizes_block_aligned(self):
        s, padded = qcomm.shard_sizes(1000, 8, 256)
        assert s % 256 == 0 and padded == 8 * s and padded >= 1000
        assert qcomm.shard_sizes(2048, 8, 256) == (256, 2048)

    def test_unknown_mode_and_bad_block_raise(self):
        with pytest.raises(ValueError, match="unknown comm-quant mode"):
            qcomm.CommQuantConfig(mode="int4")
        with pytest.raises(ValueError, match="block"):
            qcomm.CommQuantConfig(block=0)

    def test_channelwise_matches_serving_rule(self):
        w = jnp.asarray(np.random.RandomState(2).randn(64, 32).astype(
            np.float32))
        q, sc = qcomm.channelwise_absmax_int8(w, axis=0)
        assert q.dtype == jnp.int8 and sc.shape == (1, 32)
        back = qcomm.dequantize_channelwise(q, sc, jnp.float32)
        assert np.abs(np.asarray(back - w)).max() <= \
            float(jnp.max(jnp.abs(w))) / 100


# -- explicit collective API -------------------------------------------------

class TestQuantizedCollectiveAPI:
    def _allreduce(self, quantized, flag=1):
        import paddle_tpu.distributed as dist
        from paddle_tpu.tensor import Tensor
        mesh = _mesh()
        g = _group(mesh)
        paddle.set_flags({"FLAGS_quant_collectives": flag})

        def body(x):
            t = Tensor(x)
            dist.all_reduce(t, group=g, quantized=quantized)
            return t.data

        x = np.random.RandomState(0).randn(N_DEV, 600).astype(np.float32)
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                              out_specs=P("dp"), check_vma=False))
        return np.asarray(f(x)), x.sum(0, keepdims=True).repeat(N_DEV, 0)

    def test_quantized_all_reduce_close_to_exact(self):
        out, ref = self._allreduce("int8")
        rel = np.abs(out - ref).max() / np.abs(ref).max()
        assert 0 < rel < 2e-2, rel   # quantized (not exact), but close

    def test_kill_switch_restores_exact_psum_bitwise(self):
        out, _ = self._allreduce("int8", flag=0)
        exact, _ = self._allreduce(None)
        np.testing.assert_array_equal(out, exact)

    @pytest.mark.skipif(not qcomm.supports_fp8(), reason="no fp8 on jax")
    def test_fp8_mode(self):
        out, ref = self._allreduce("fp8")
        assert np.abs(out - ref).max() / np.abs(ref).max() < 8e-2

    def test_eager_single_controller_identity(self):
        # no shard_map: the world reduction is identity (no wire), the
        # quantized entry point must keep the exact fallback
        import paddle_tpu.distributed as dist
        t = paddle.to_tensor(np.ones((4, 4), np.float32))
        before = np.asarray(t.numpy())
        dist.quantized_all_reduce(t)
        np.testing.assert_array_equal(np.asarray(t.numpy()), before)

    def test_quantized_reduce_scatter(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu.tensor import Tensor
        mesh = _mesh()
        g = _group(mesh)
        x = np.random.RandomState(3).randn(
            N_DEV, N_DEV, 40).astype(np.float32)

        def body(xs):
            xs = xs[0]          # (N_DEV, 40) local contribution rows
            parts = [Tensor(xs[i]) for i in range(N_DEV)]
            t = Tensor(jnp.zeros_like(xs[0]))
            dist.quantized_reduce_scatter(t, parts, group=g)
            return t.data[None]

        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                              out_specs=P("dp"), check_vma=False))
        out = np.asarray(f(x))                 # rank i keeps shard i
        ref = x.sum(axis=0)                    # (N_DEV, 40)
        rel = np.abs(out - ref).max() / np.abs(ref).max()
        assert 0 < rel < 2e-2, rel


# -- TrainStep / ShardingPlan gradient-sync seam ----------------------------

def _train(grad_sync=None, ef=False, flag=1, steps=4, mode_block=None,
           seed=0, dims=(8, 32, 4)):
    paddle.set_flags({"FLAGS_quant_collectives": flag})
    if mode_block:
        paddle.set_flags({"FLAGS_quant_collectives_block": mode_block})
    paddle.seed(seed)
    mesh = _mesh()
    d_in, d_hid, d_out = dims
    m = nn.Sequential(nn.Linear(d_in, d_hid), nn.ReLU(),
                      nn.Linear(d_hid, d_out))
    o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
    plan = ShardingPlan(mesh, grad_sync=grad_sync,
                        grad_sync_error_feedback=ef)
    x = np.random.RandomState(0).randn(16, d_in).astype(np.float32)
    y = np.random.RandomState(1).randn(16, d_out).astype(np.float32)

    def step_fn(xb, yb):
        return F.mse_loss(m(xb), yb)

    ts = paddle.jit.TrainStep(m, o, step_fn, shard=plan)
    losses = [float(ts(paddle.to_tensor(x), paddle.to_tensor(y)).numpy())
              for _ in range(steps)]
    weights = {k: np.asarray(t.data) for k, t in m.state_dict().items()}
    return losses, weights, ts


_FP32_REF = {}


def _fp32_reference():
    """The unquantized GSPMD-sync run several tests compare against —
    computed once per session (each _train costs a TrainStep compile)."""
    if "ref" not in _FP32_REF:
        _FP32_REF["ref"] = _train(grad_sync=None)
    losses, weights, ts = _FP32_REF["ref"]
    return list(losses), weights, ts


class TestQuantizedGradSync:
    def test_kill_switch_bitwise_parity_through_trainstep(self):
        """ACCEPTANCE: FLAGS_quant_collectives=0 restores the implicit
        GSPMD-psum TrainStep bitwise — identical losses AND weights to a
        plan that never asked for quantized sync."""
        l_ref, w_ref, _ = _fp32_reference()
        l_off, w_off, ts = _train(grad_sync="int8", flag=0)
        assert l_ref == l_off
        assert ts._quant is None         # the quantized path never built
        for k in w_ref:
            np.testing.assert_array_equal(w_ref[k], w_off[k])

    def test_quantized_sync_tracks_fp32_trajectory(self):
        l_ref, w_ref, _ = _fp32_reference()
        l_q, w_q, ts = _train(grad_sync="int8")
        assert ts._quant is not None
        # near-identical first loss (quantization only touches grads;
        # the two compilations may round the loss reduction differently
        # — GSPMD global mean vs per-shard mean + pmean), trajectory
        # within a tight tolerance after that
        assert abs(l_q[0] - l_ref[0]) <= 1e-5 * max(abs(l_ref[0]), 1.0)
        assert max(abs(a - b) for a, b in zip(l_ref, l_q)) < 5e-3
        assert any(not np.array_equal(w_ref[k], w_q[k]) for k in w_ref), \
            "quantized sync should not be bitwise-identical to fp32"

    def test_error_feedback_state_carried_and_sharded(self):
        l_q, _, ts = _train(grad_sync="int8", ef=True)
        axis, n, cfg = ts._quant
        assert cfg.error_feedback and n == N_DEV
        assert ts._ef_state, "EF residuals were never allocated"
        for k, v in ts._ef_state.items():
            assert v.shape[0] == N_DEV and v.shape[1] % cfg.block == 0
            # residual is live state: quantization error is nonzero
        total = sum(float(jnp.abs(v).sum()) for v in ts._ef_state.values())
        assert total > 0.0
        l_ref, _, _ = _fp32_reference()
        assert max(abs(a - b) for a, b in zip(l_ref, l_q)) < 5e-3

    @pytest.mark.skipif(not qcomm.supports_fp8(), reason="no fp8 on jax")
    def test_fp8_grad_sync(self):
        l_ref, _, _ = _fp32_reference()
        l_q, _, ts = _train(grad_sync="fp8", ef=True)
        assert ts._quant[2].mode == "fp8"
        assert max(abs(a - b) for a, b in zip(l_ref, l_q)) < 3e-2

    def test_block_size_flag_consumed(self):
        _, _, ts = _train(grad_sync="int8", mode_block=64)
        assert ts._quant[2].block == 64

    def test_guards(self):
        mesh = _mesh()
        with pytest.raises(ValueError, match="stage"):
            ShardingPlan(mesh, stage=1, grad_sync="int8")
        m = nn.Linear(4, 4)
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        plan = ShardingPlan(mesh, grad_sync="int8")
        from paddle_tpu.amp import GradScaler
        with pytest.raises(ValueError, match="GradScaler"):
            paddle.jit.TrainStep(m, o, lambda x: m(x).mean(),
                                 scaler=GradScaler(), shard=plan)
        with pytest.raises(ValueError, match="accumulate_steps"):
            paddle.jit.TrainStep(m, o, lambda x: m(x).mean(), shard=plan,
                                 accumulate_steps=2)
        # no usable data axis: a 1-device mesh cannot host the chain
        tiny = ShardingPlan(Mesh(np.asarray(jax.devices()[:1]), ("dp",)),
                            grad_sync="int8")
        with pytest.raises(ValueError, match="exactly one"):
            tiny.quant_sync_axis()


# -- wire-byte telemetry -----------------------------------------------------

class TestWireTelemetry:
    def test_grad_sync_wire_bytes_and_ratio(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import metrics
        obs.enable(True)
        try:
            # realistically-sized layers: wire accounting includes the
            # block/shard PADDING, so a 4-element bias costs a whole
            # padded shard per rank — the compression win is real only
            # for tensors >> nranks * block, exactly the gradient regime
            _train(grad_sync="int8", steps=1, dims=(64, 512, 8))
            snap = metrics.snapshot()
            logical = snap["counters"]["collective.bytes_total"][
                "op=grad_sync"]
            wire = snap["counters"]["collective.wire_bytes_total"][
                "op=grad_sync"]
            ratio = snap["gauges"]["collective.compression_ratio"][
                "op=grad_sync"]
            assert 0 < wire < logical
            # symmetric-phase physical compression: 4 / (1 + 4/block)
            assert abs(ratio - 4.0 / (1.0 + 4.0 / 256)) < 1e-6
            # logical counter keeps the payload-entering convention:
            # sum of the f32 grad byte sizes (counted once per compile)
            assert logical == (64 * 512 + 512 + 512 * 8 + 8) * 4
        finally:
            obs.enable(False)

    def test_exact_ops_report_wire_equal_to_logical(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import metrics
        obs.enable(True)
        try:
            t = paddle.to_tensor(np.ones((8, 4), np.float32))
            dist.all_reduce(t)
            snap = metrics.snapshot()
            assert snap["counters"]["collective.wire_bytes_total"][
                "op=all_reduce"] == \
                snap["counters"]["collective.bytes_total"]["op=all_reduce"]
        finally:
            obs.enable(False)
