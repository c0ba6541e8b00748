"""ZeRO sharded optimizer update (ISSUE 16, arxiv 2004.13336):
ShardingPlan(zero=1|2) reduce-scatters grads over the DP axis, updates
each rank's flat 1/nranks shard of params with shard-shaped accumulator
state, and all-gathers params back to replicated. Covers the FLAGS_zero
bitwise kill switch, convergence vs the replicated update, the per-rank
state-memory win, composition with grad_sync="int8" + error feedback,
the world-resize state conversion, and the guard rails."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed.sharding import (
    ShardingPlan, convert_zero_opt_state)
from paddle_tpu.quantization import comm as qcomm

N_DEV = 8


def _mesh(n=N_DEV):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("dp",))


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    paddle.set_flags({"FLAGS_zero": 1, "FLAGS_quant_collectives": 1,
                      "FLAGS_quant_collectives_block": 256})


def _train(zero=0, grad_sync=None, ef=False, flag=1, steps=4, seed=0,
           dims=(8, 32, 4), optimizer=None, n=N_DEV):
    paddle.set_flags({"FLAGS_zero": flag})
    paddle.seed(seed)
    mesh = _mesh(n)
    d_in, d_hid, d_out = dims
    m = nn.Sequential(nn.Linear(d_in, d_hid), nn.ReLU(),
                      nn.Linear(d_hid, d_out))
    o = (optimizer or opt.AdamW)(learning_rate=0.01,
                                 parameters=m.parameters())
    plan = ShardingPlan(mesh, zero=zero, grad_sync=grad_sync,
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


_REF = {}


def _replicated_reference():
    """The zero=0 replicated run most tests compare against — computed
    once per session (each _train costs a TrainStep compile)."""
    if "ref" not in _REF:
        _REF["ref"] = _train(zero=0)
    losses, weights, ts = _REF["ref"]
    return list(losses), weights, ts


class TestZeroTrainStep:
    def test_kill_switch_bitwise_parity_through_trainstep(self):
        """ACCEPTANCE: FLAGS_zero=0 restores the replicated TrainStep
        bitwise — identical losses AND weights to a plan that never
        asked for ZeRO."""
        l_ref, w_ref, _ = _replicated_reference()
        l_off, w_off, ts = _train(zero=2, flag=0)
        assert l_ref == l_off
        assert ts._zero is None          # the ZeRO path never built
        for k in w_ref:
            np.testing.assert_array_equal(w_ref[k], w_off[k])

    def test_zero2_tracks_replicated_trajectory(self):
        """Step-0 loss identical within float-order tolerance, trajectory
        within 3% — the exact reduce-scatter only re-associates the
        gradient mean."""
        l_ref, w_ref, _ = _replicated_reference()
        l_z, w_z, ts = _train(zero=2)
        assert ts._zero is not None and ts._zero[2] == 2
        assert abs(l_z[0] - l_ref[0]) <= 1e-5 * max(abs(l_ref[0]), 1.0)
        assert max(abs(a - b) / max(abs(a), 1e-3)
                   for a, b in zip(l_ref, l_z)) < 3e-2
        for k in w_ref:
            np.testing.assert_allclose(w_ref[k], w_z[k], rtol=2e-4,
                                       atol=2e-5)

    def test_zero1_tracks_replicated_trajectory(self):
        l_ref, _, _ = _replicated_reference()
        l_z, _, ts = _train(zero=1)
        assert ts._zero is not None and ts._zero[2] == 1
        assert abs(l_z[0] - l_ref[0]) <= 1e-5 * max(abs(l_ref[0]), 1.0)
        assert max(abs(a - b) / max(abs(a), 1e-3)
                   for a, b in zip(l_ref, l_z)) < 3e-2

    def test_opt_state_sharded_per_rank_reduction(self):
        """THE HBM WIN: every accumulator slot is a flat padded vector
        sharded over dp — one (s,)-slice per rank, ~nranks x smaller
        than the replicated footprint. The padding caveat is covered by
        the default dims: the 4-element output bias (< nranks) rounds
        up to one element per rank."""
        _, _, ts_ref = _replicated_reference()
        _, _, ts = _train(zero=2)
        o = ts.optimizer
        assert o._state, "no optimizer state materialized"
        for (pid, slot), v in o._state.items():
            assert v.ndim == 1, (slot, v.shape)
            assert v.sharding.spec == P("dp"), (slot, v.sharding)
            numel = next(int(p.data.size) for p in o._parameter_list
                         if id(p) == pid)
            s, padded = qcomm.shard_sizes(numel, N_DEV, 1)
            assert v.shape == (padded,)
            # tail padding never reaches the weights and stays zero
            np.testing.assert_array_equal(np.asarray(v)[numel:], 0.0)
        repl = ts_ref.opt_state_bytes_per_rank()
        shrd = ts.opt_state_bytes_per_rank()
        assert shrd * N_DEV / 1.6 <= repl, (shrd, repl)

    def test_zero_composes_with_quantized_grad_sync_and_ef(self):
        """ACCEPTANCE: zero=2 + grad_sync="int8" + error feedback — the
        grad half rides phase 1 of the EQuARX chain, EF residuals are
        carried dp-sharded, and the trajectory stays close to the
        replicated fp32 run."""
        l_ref, w_ref, _ = _replicated_reference()
        l_q, w_q, ts = _train(zero=2, grad_sync="int8", ef=True)
        axis, nranks, stage, cfg, block = ts._zero
        assert stage == 2 and cfg is not None and cfg.error_feedback
        assert block == cfg.block == 256
        assert ts._ef_state, "EF residuals were never allocated"
        for k, v in ts._ef_state.items():
            assert v.shape[0] == N_DEV and v.shape[1] % cfg.block == 0
        total = sum(float(jnp.abs(v).sum()) for v in ts._ef_state.values())
        assert total > 0.0
        assert abs(l_q[0] - l_ref[0]) <= 1e-5 * max(abs(l_ref[0]), 1.0)
        assert max(abs(a - b) for a, b in zip(l_ref, l_q)) < 3e-2
        assert any(not np.array_equal(w_ref[k], w_q[k]) for k in w_ref), \
            "quantized wire should not be bitwise-identical to fp32"

    def test_quant_kill_switch_reverts_wire_to_exact(self):
        """FLAGS_quant_collectives=0 under an armed zero plan keeps the
        SHARDED update but drops the wire back to the exact
        psum_scatter — same trajectory as the plain zero=2 run."""
        paddle.set_flags({"FLAGS_quant_collectives": 0})
        l_q, _, ts = _train(zero=2, grad_sync="int8", ef=True)
        assert ts._zero is not None and ts._zero[3] is None
        assert ts._zero[4] == 1 and not ts._ef_state
        l_z, _, _ = _train(zero=2)
        assert l_q == l_z

    def test_opt_state_bytes_gauge_recorded(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import metrics
        obs.enable(True)
        try:
            _, _, ts = _train(zero=2, steps=1)
            snap = metrics.snapshot()
            series = snap["gauges"]["train.opt_state_bytes"]
            val = series[f"executable={ts._exec_tag}"]
            assert val == ts.opt_state_bytes_per_rank() > 0
        finally:
            obs.enable(False)

    def test_state_conversion_to_replicated_and_back(self):
        """convert_zero_opt_state: flat padded slots strip their tail
        padding back to param-shaped state (plan=None) and re-pad to a
        DIFFERENT world's layout (plan over 4 devices) — the
        world-resize restore recipe, value-exact both ways."""
        _, _, ts = _train(zero=2, steps=2)
        o = ts.optimizer
        names = {id(p): p.name or str(i)
                 for i, p in enumerate(o._parameter_list)}
        m_params = {id(p): p for p in o._parameter_list}
        saved = o.state_dict()
        del saved["@step"]
        # -> replicated (world=1 restore)
        repl = convert_zero_opt_state(saved, o, plan=None)
        for (pid, slot), v in o._state.items():
            p = m_params[pid]
            key = f"{names[pid]}.{slot}"
            assert repl[key].shape == p.data.shape
            np.testing.assert_array_equal(
                np.asarray(repl[key]).ravel(),
                np.asarray(v)[:int(p.data.size)])
        # -> world=4 layout
        plan4 = ShardingPlan(_mesh(4), zero=2)
        conv4 = convert_zero_opt_state(saved, o, plan=plan4)
        by_name = {names[id(p)]: p for p in o._parameter_list}
        for k, v in conv4.items():
            p = by_name[k.rsplit(".", 1)[0]]
            s4, padded4 = plan4.zero_layout(int(p.data.size))
            assert v.shape == (padded4,)
            assert v.sharding.spec == P("dp")
            np.testing.assert_array_equal(
                np.asarray(v)[:int(p.data.size)],
                np.asarray(saved[k])[:int(p.data.size)])

    def test_resume_from_converted_state_matches(self):
        """A zero=2 run restored from its own converted-to-replicated
        state continues with the same next loss as the uninterrupted
        replicated run would (the update maths agree)."""
        l_z, _, ts = _train(zero=2, steps=3)
        o = ts.optimizer
        saved = o.state_dict()
        repl = convert_zero_opt_state(
            {k: v for k, v in saved.items() if k != "@step"}, o, plan=None)
        repl["@step"] = saved["@step"]
        # fresh replicated model+opt, same weights/state -> same losses
        paddle.seed(0)
        m2 = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))
        for (k, t2), (_, t1) in zip(m2.state_dict().items(),
                                    ts.model.state_dict().items()):
            # by value: the next ts() call DONATES t1's buffer
            t2.data = jnp.asarray(np.asarray(t1.data))
        o2 = opt.AdamW(learning_rate=0.01, parameters=m2.parameters())
        o2.set_state_dict(repl)
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(16, 8).astype(np.float32))
        y = paddle.to_tensor(
            np.random.RandomState(1).randn(16, 4).astype(np.float32))
        next_z4 = float(ts(x, y).numpy())   # loss with post-step-3 weights
        next_z5 = float(ts(x, y).numpy())   # loss with post-step-4 weights
        loss4 = F.mse_loss(m2(x), y)
        assert abs(float(loss4.numpy()) - next_z4) < \
            1e-3 * max(abs(next_z4), 1.0)
        loss4.backward()
        o2.step()                            # eager replicated step 4
        o2.clear_grad()
        loss5 = float(F.mse_loss(m2(x), y).numpy())
        assert abs(loss5 - next_z5) < 1e-3 * max(abs(next_z5), 1.0)


class TestZeroGuards:
    def test_invalid_mode(self):
        with pytest.raises(ValueError, match="zero"):
            ShardingPlan(_mesh(), zero=3)

    def test_stage_guard_unified_and_names_zero(self):
        """Satellite: the stage!=0 guard is ONE diagnostic naming both
        knobs — grad_sync-only, zero-only, and combined all fail fast
        with a message that names zero=."""
        with pytest.raises(ValueError, match="zero="):
            ShardingPlan(_mesh(), stage=1, grad_sync="int8")
        with pytest.raises(ValueError, match="stage"):
            ShardingPlan(_mesh(), stage=1, zero=2)
        with pytest.raises(ValueError, match="grad_sync='int8' and zero=1"):
            ShardingPlan(_mesh(), stage=2, grad_sync="int8", zero=1)

    def test_trainstep_guards(self):
        m = nn.Linear(4, 4)
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        plan = ShardingPlan(_mesh(), zero=2)
        from paddle_tpu.amp import GradScaler
        with pytest.raises(ValueError, match="GradScaler"):
            paddle.jit.TrainStep(m, o, lambda x: m(x).mean(),
                                 scaler=GradScaler(), shard=plan)
        with pytest.raises(ValueError, match="accumulate_steps"):
            paddle.jit.TrainStep(m, o, lambda x: m(x).mean(), shard=plan,
                                 accumulate_steps=2)
        oc = opt.AdamW(learning_rate=0.01, parameters=m.parameters(),
                       grad_clip=nn.ClipGradByGlobalNorm(1.0))
        with pytest.raises(ValueError, match="grad_clip"):
            paddle.jit.TrainStep(m, oc, lambda x: m(x).mean(), shard=plan)
        ol = opt.Lamb(learning_rate=0.01, parameters=m.parameters())
        with pytest.raises(ValueError, match="elementwise"):
            paddle.jit.TrainStep(m, ol, lambda x: m(x).mean(), shard=plan)

    def test_master_weights_guard(self):
        m = nn.Linear(4, 4)
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        o._master_weights[id(m.weight)] = jnp.zeros((4, 4), jnp.float32)
        plan = ShardingPlan(_mesh(), zero=1)
        with pytest.raises(ValueError, match="master weights"):
            paddle.jit.TrainStep(m, o, lambda x: m(x).mean(), shard=plan)


class TestZeroCollectives:
    def test_rs_shard_matches_mean_and_ag_roundtrips(self):
        """zero_grad_reduce_scatter shards the exact mean (both stages);
        zero_param_all_gather reassembles the padded flat vector."""
        from jax import shard_map

        from paddle_tpu.distributed.collective import (
            zero_grad_reduce_scatter, zero_param_all_gather)
        mesh = _mesh()
        numel = 100                     # pads: s=13, padded=104
        s, padded = qcomm.shard_sizes(numel, N_DEV, 1)
        x = np.random.RandomState(0).randn(N_DEV, numel).astype(np.float32)

        def body(rows, stage):
            g = rows[0]
            shard, _ = zero_grad_reduce_scatter(
                g, axis="dp", nranks=N_DEV, stage=stage)
            return zero_param_all_gather(shard, axis="dp")[None]

        for stage in (1, 2):
            f = jax.jit(shard_map(
                lambda r, st=stage: body(r, st), mesh=mesh,
                in_specs=P("dp"), out_specs=P("dp"), check_vma=False))
            out = np.asarray(f(x))      # every rank: the padded mean
            ref = np.pad(x.mean(0), (0, padded - numel))
            for r in range(N_DEV):
                np.testing.assert_allclose(out[r], ref, rtol=1e-5,
                                           atol=1e-6)
