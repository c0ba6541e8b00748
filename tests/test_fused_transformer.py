"""The dense decoder's hot path (ISSUE 20; one path since ISSUE 29):
fused residual+RMSNorm and SwiGLU Pallas kernels, fused QKV+RoPE
prologue, remat-policy knob and the donation audit.

Kernel tests mirror tests/test_ragged_attention.py's split: fallback
parity (the jnp route IS the unfused math, bitwise), interpret-mode
Pallas parity (fwd + grads vs that same fallback), explicit
use_pallas=True raising on unaligned shapes instead of silently timing
the fallback, and the autotune key being consulted. The grad harness is
shared between the new kernels and the pre-existing rms_norm custom_vjp
(satellite: bwd vs jnp autodiff at fp32 AND bf16).
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.kernels import fused_norm_residual as fnr
from paddle_tpu.kernels import rope
from paddle_tpu.kernels import swiglu as sg
from paddle_tpu.kernels.rms_norm import rms_norm
from paddle_tpu.models import llama
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny


# ---------------------------------------------------------------- harness

def _weighted_sum(out):
    """Scalar loss over one-or-tuple outputs; distinct weights per
    output so swapped/aliased outputs can't cancel in the grad check."""
    if not isinstance(out, tuple):
        out = (out,)
    return sum((i + 2.0) * jnp.sum(o.astype(jnp.float32) ** 2)
               for i, o in enumerate(out))


def _check_grads(fn, ref, args, rtol, atol):
    """jax.grad of fn vs ref w.r.t. every arg — the shared harness for
    rms_norm and both new kernels (custom_vjp bwd vs jnp autodiff, or
    Pallas bwd vs fallback bwd)."""
    argnums = tuple(range(len(args)))
    got = jax.grad(lambda *a: _weighted_sum(fn(*a)), argnums)(*args)
    want = jax.grad(lambda *a: _weighted_sum(ref(*a)), argnums)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)


def _rand(shape, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)


# ------------------------------------------- rms_norm grad equivalence

def _rms_autodiff_ref(x, w, eps=1e-6):
    """The rms_norm fallback math WITHOUT the custom_vjp wrapper, so
    jax.grad differentiates it with plain autodiff."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


class TestRmsNormGradEquivalence:
    def test_fp32_bwd_matches_autodiff(self):
        x = _rand((4, 6, 96), jnp.float32)
        w = _rand((96,), jnp.float32, seed=1) * 0.1 + 1.0
        np.testing.assert_allclose(np.asarray(rms_norm(x, w)),
                                   np.asarray(_rms_autodiff_ref(x, w)),
                                   rtol=0, atol=0)
        _check_grads(rms_norm, _rms_autodiff_ref, (x, w),
                     rtol=1e-5, atol=1e-4)

    def test_bf16_bwd_matches_autodiff(self):
        x = _rand((4, 6, 96), jnp.bfloat16)
        w = (_rand((96,), jnp.float32, seed=1) * 0.1 + 1.0
             ).astype(jnp.bfloat16)
        # the analytic bwd and autodiff round to bf16 at different
        # points; agreement is to bf16 resolution, not bitwise
        _check_grads(rms_norm, _rms_autodiff_ref, (x, w),
                     rtol=0.06, atol=0.3)


# ------------------------------------------- fused residual + RMSNorm

def _fnr_unfused_ref(x, r, w, eps=1e-6):
    """The unfused two-op sequence the kill switch runs: residual add
    (rounded to the stream dtype) then rms_norm — the parity target."""
    h = (x.astype(jnp.float32) + r.astype(jnp.float32)).astype(x.dtype)
    return _rms_autodiff_ref(h, w, eps), h


class TestFusedNormResidual:
    def test_fallback_matches_unfused_sequence_bitwise(self):
        for dtype in (jnp.float32, jnp.bfloat16):
            x = _rand((2, 8, 256), dtype)
            r = _rand((2, 8, 256), dtype, seed=1)
            w = _rand((256,), dtype, seed=2) * 0.1 + 1.0
            y, h = fnr.fused_add_rms_norm(x, r, w, use_pallas=False)
            yr, hr = _fnr_unfused_ref(x, r, w)
            assert np.array_equal(np.asarray(h, np.float32),
                                  np.asarray(hr, np.float32))
            assert np.array_equal(np.asarray(y, np.float32),
                                  np.asarray(yr, np.float32))

    def test_interpret_parity_fwd(self):
        x = _rand((4, 8, 256), jnp.float32)
        r = _rand((4, 8, 256), jnp.float32, seed=1)
        w = _rand((256,), jnp.float32, seed=2) * 0.1 + 1.0
        y, h = fnr.fused_add_rms_norm(x, r, w, use_pallas=True)
        yr, hr = _fnr_unfused_ref(x, r, w)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)

    def test_interpret_parity_grads(self):
        x = _rand((2, 8, 256), jnp.float32)
        r = _rand((2, 8, 256), jnp.float32, seed=1)
        w = _rand((256,), jnp.float32, seed=2) * 0.1 + 1.0
        _check_grads(
            lambda *a: fnr.fused_add_rms_norm(*a, use_pallas=True),
            lambda *a: fnr.fused_add_rms_norm(*a, use_pallas=False),
            (x, r, w), rtol=1e-5, atol=1e-4)

    def test_interpret_parity_ragged_last_block(self):
        # 40 rows in blocks of 16: the last block is padded on read and
        # its out-of-range rows dropped on write
        x = _rand((40, 256), jnp.float32)
        r = _rand((40, 256), jnp.float32, seed=1)
        w = _rand((256,), jnp.float32, seed=2) * 0.1 + 1.0
        y, h = fnr.fused_add_rms_norm(x, r, w, use_pallas=True,
                                      block_rows=16)
        yr, hr = _fnr_unfused_ref(x, r, w)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)

    def test_fallback_grads_match_unfused_autodiff(self):
        """The custom bwd vs plain autodiff of the unfused sequence —
        the tape LlamaDecoderLayer builds under sequence parallelism."""
        for dtype, rtol, atol in ((jnp.float32, 1e-5, 1e-4),
                                  (jnp.bfloat16, 0.06, 0.5)):
            x = _rand((2, 8, 256), dtype)
            r = _rand((2, 8, 256), dtype, seed=1)
            w = _rand((256,), dtype, seed=2) * 0.1 + 1.0
            _check_grads(
                lambda *a: fnr.fused_add_rms_norm(*a, use_pallas=False),
                _fnr_unfused_ref, (x, r, w), rtol=rtol, atol=atol)

    def test_explicit_use_pallas_rejects_unaligned(self):
        x = _rand((2, 4, 200), jnp.float32)
        with pytest.raises(ValueError, match="Mosaic-aligned"):
            fnr.fused_add_rms_norm(x, x, jnp.ones((200,)),
                                   use_pallas=True)

    def test_force_pallas_hook_dispatches_interpreter(self, monkeypatch):
        called = []
        real = fnr._fwd_kernel

        def spy(*a, **k):
            called.append(1)
            return real(*a, **k)

        monkeypatch.setattr(fnr, "_fwd_kernel", spy)
        monkeypatch.setattr(fnr, "_FORCE_PALLAS", True)
        x = _rand((2, 4, 256), jnp.float32)
        fnr.fused_add_rms_norm(x, x, jnp.ones((256,)))
        assert called, "_FORCE_PALLAS must route auto dispatch to Pallas"

    def test_block_rows_consults_autotune(self, monkeypatch):
        from paddle_tpu.kernels import autotune
        key = autotune.cache_key("fused_norm", H=fnr._size_class(256))
        monkeypatch.setattr(autotune, "lookup",
                            lambda k: [64] if k == key else None)
        assert fnr._block_rows(512, 256, 4) == 64
        # default chain: 256 rows
        monkeypatch.setattr(autotune, "lookup", lambda k: None)
        assert fnr._block_rows(512, 256, 4) == 256
        # an override is rounded down to whole (16, 128) tiles
        assert fnr._block_rows(512, 256, 4, block_rows=100) == 96
        # few rows go in one block; wide rows shrink the block to VMEM
        assert fnr._block_rows(5, 256, 4) == 5
        assert fnr._block_rows(8192, 4096, 2) == 160
        assert fnr._block_rows(8192, 4096, 4) == 80


# --------------------------------------------------------------- swiglu

class TestSwiGLU:
    def test_fallback_is_exact_unfused_expression(self):
        for dtype in (jnp.float32, jnp.bfloat16):
            a = _rand((3, 8, 256), dtype)
            w = _rand((256, 512), dtype, seed=1) * 0.05
            got = sg.swiglu(a, w, use_pallas=False)
            gu = a @ w
            want = jax.nn.silu(gu[..., :256]) * gu[..., 256:]
            assert np.array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))

    def test_interpret_parity_fwd(self):
        a = _rand((64, 256), jnp.float32)
        w = _rand((256, 512), jnp.float32, seed=1) * 0.05
        got = sg.swiglu(a, w, use_pallas=True)
        want = sg.swiglu(a, w, use_pallas=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_interpret_parity_grads(self):
        a = _rand((32, 256), jnp.float32)
        w = _rand((256, 512), jnp.float32, seed=1) * 0.05
        _check_grads(lambda *x: sg.swiglu(*x, use_pallas=True),
                     lambda *x: sg.swiglu(*x, use_pallas=False),
                     (a, w), rtol=1e-4, atol=1e-4)

    def test_interpret_parity_ragged_last_row_block(self):
        # 40 rows in blocks of 16: the weight gradient sums over rows,
        # so what the padded last block read past row 40 is masked
        a = _rand((40, 256), jnp.float32)
        w = _rand((256, 512), jnp.float32, seed=1) * 0.05
        _check_grads(
            lambda *x: sg.swiglu(*x, use_pallas=True, blocks=(16, 128)),
            lambda *x: sg.swiglu(*x, use_pallas=False),
            (a, w), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("rows, blocks", [
        # (fwd, da, dw): dw on its own blocks, ragged rows
        (40, ((16, 128), (16, 128), (16, 256))),
        # da and dw ragged at different rows
        (40, ((16, 128), (32, 256), (16, 128))),
        # 384 does not divide 2M = 512: dw's last column block is partial
        (72, ((32, 256), (16, 128), (32, 384))),
        (40, None),                  # few rows: one block in each kernel
    ], ids=["dw-own-blocks", "both-ragged", "dw-partial-columns",
            "one-row-block"])
    def test_interpret_parity_backward_pair(self, rows, blocks):
        # swiglu_bwd_da hands the gate | up cotangent to swiglu_bwd_dw
        # through HBM: whatever the two kernels' blocks, rows past T and
        # columns past 2M stay out of both gradients
        a = _rand((rows, 256), jnp.float32)
        w = _rand((256, 512), jnp.float32, seed=1) * 0.05
        _check_grads(
            lambda *x: sg.swiglu(*x, use_pallas=True, blocks=blocks),
            lambda *x: sg.swiglu(*x, use_pallas=False),
            (a, w), rtol=1e-4, atol=1e-4)

    def test_interpret_parity_grads_bf16(self):
        # dg/du are formed and summed in f32 and cross HBM in the
        # activation's dtype, as the unfused expression's gradient does:
        # the pair stays nearer the f32 gradient than that expression in
        # bf16, and within 2^-8 of its norm
        a = _rand((40, 256), jnp.bfloat16)
        w = _rand((256, 512), jnp.bfloat16, seed=1) * 0.05

        def grads(route, *x):
            return jax.grad(lambda *x_: _weighted_sum(sg.swiglu(
                *x_, use_pallas=route, blocks=(16, 128))), (0, 1))(*x)

        exact = grads(False, a.astype(jnp.float32), w.astype(jnp.float32))
        for got, unfused, want in zip(grads(True, a, w), grads(False, a, w),
                                      exact):
            assert got.dtype == jnp.bfloat16
            want = np.asarray(want)
            err, unfused_err = (
                np.linalg.norm(np.asarray(x, np.float32) - want)
                / np.linalg.norm(want) for x in (got, unfused))
            assert err < unfused_err and err < 2 ** -8

    def test_dw_is_one_array_gate_columns_first(self, monkeypatch):
        a = _rand((40, 256), jnp.float32)
        wg = _rand((256, 256), jnp.float32, seed=1) * 0.05
        wu = _rand((256, 256), jnp.float32, seed=2) * 0.05
        g = _rand((40, 256), jnp.float32, seed=3)
        recomputed = []
        real = sg._dgu_tile
        monkeypatch.setattr(sg, "_dgu_tile", lambda *r: (
            recomputed.append(1), real(*r))[1])
        da, dw = sg._bwd_impl(a, jnp.concatenate([wg, wu], axis=-1), g,
                              True, (16, 128))
        # g and u are recomputed in ONE kernel: swiglu_bwd_dw is a matmul
        assert len(recomputed) == 1
        assert dw.shape == (256, 512) and da.shape == a.shape
        _, vjp = jax.vjp(
            lambda a_, g_, u_: jax.nn.silu(a_ @ g_) * (a_ @ u_), a, wg, wu)
        want_da, want_dwg, want_dwu = vjp(g)
        for got, want in ((da, want_da), (dw[:, :256], want_dwg),
                          (dw[:, 256:], want_dwu)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    def test_blocks_override_changes_blocking_not_results(self):
        a = _rand((64, 256), jnp.float32)
        w = _rand((256, 512), jnp.float32, seed=1) * 0.05
        base = np.asarray(sg.swiglu(a, w, use_pallas=True))
        for blocks in ((16, 128), (32, 256)):
            out = np.asarray(sg.swiglu(a, w, use_pallas=True,
                                       blocks=blocks))
            np.testing.assert_allclose(out, base, rtol=1e-5, atol=1e-5)

    def test_explicit_use_pallas_rejects_unaligned(self):
        a = _rand((8, 256), jnp.float32)
        with pytest.raises(ValueError, match="Mosaic-aligned"):
            sg.swiglu(a, _rand((256, 200), jnp.float32), use_pallas=True)

    def test_force_pallas_hook_dispatches_interpreter(self, monkeypatch):
        called = []
        real = sg._fwd_kernel

        def spy(*a, **k):
            called.append(1)
            return real(*a, **k)

        monkeypatch.setattr(sg, "_fwd_kernel", spy)
        monkeypatch.setattr(sg, "_FORCE_PALLAS", True)
        sg.swiglu(_rand((8, 256), jnp.float32),
                  _rand((256, 512), jnp.float32, seed=1))
        assert called, "_FORCE_PALLAS must route auto dispatch to Pallas"

    def test_blocks_consult_autotune(self, monkeypatch):
        from paddle_tpu.kernels import autotune
        key = autotune.cache_key("swiglu", M=sg._size_class(256))
        monkeypatch.setattr(autotune, "lookup",
                            lambda k: [64, 128] if k == key else None)
        assert sg._blocks("fwd", 512, 128, 256, 4) == (64, 128)
        # default chain: (256, 512), columns shrunk to a 128-multiple
        # divisor of M
        monkeypatch.setattr(autotune, "lookup", lambda k: None)
        assert sg._blocks("fwd", 512, 128, 256, 4) == (256, 256)
        # the shipped widths: column blocks tile (8, 128) and divide M
        assert sg._blocks("fwd", 8192, 1024, 2816, 2) == (256, 256)
        assert sg._blocks("fwd", 8192, 2048, 5504, 2) == (256, 128)
        assert sg._blocks("fwd", 8192, 4096, 11008, 2) == (256, 256)
        # few rows go in one block
        assert sg._blocks("fwd", 4, 4096, 11008, 2) == (4, 256)

    @pytest.mark.parametrize("kernel, key", [
        ("da", "swiglu_bwd_da"), ("dw", "swiglu_bwd_dw")])
    def test_backward_blocks_consult_autotune(self, monkeypatch, kernel,
                                              key):
        from paddle_tpu.kernels import autotune
        key = autotune.cache_key(key, M=sg._size_class(256))
        monkeypatch.setattr(autotune, "lookup",
                            lambda k: [64, 128] if k == key else None)
        assert sg._blocks(kernel, 512, 128, 256, 4) == (64, 128)
        # the other kernels' winners are not this kernel's
        assert sg._blocks("fwd", 512, 128, 256, 4) == (256, 256)

    def test_backward_blocks_from_the_shapes(self, monkeypatch):
        from paddle_tpu.kernels import autotune
        monkeypatch.setattr(autotune, "lookup", lambda k: None)
        # the benchmark's cells, a device: da keeps a column block that
        # divides M under twice the forward's rows
        assert sg._blocks("da", 4096, 4096, 11008, 2) == (512, 256)
        assert sg._blocks("da", 4096, 4096, 5504, 2) == (512, 128)
        # dw runs over the flat 2M columns and is not held to a divisor
        assert sg._blocks("dw", 4096, 4096, 11008, 2) == (1024, 512)
        assert sg._blocks("dw", 4096, 4096, 5504, 2) == (1024, 512)
        # what does not fit: da gives up columns, then rows; dw rows
        assert sg._blocks("da", 4096, 4096, 11008, 4) == (256, 256)
        assert sg._blocks("dw", 4096, 4096, 11008, 4) == (512, 512)
        assert sg._blocks("dw", 4096, 8192, 11008, 2) == (256, 512)
        # a narrow layer is one column block; few rows one row block
        assert sg._blocks("dw", 40, 256, 128, 4) == (40, 256)
        # one override for every kernel, or one each (fwd, da, dw)
        each = ((16, 128), (32, 256), (64, 384))
        assert [sg._blocks(k, 512, 256, 256, 4, each)
                for k in ("fwd", "da", "dw")] == list(each)
        assert [sg._blocks(k, 512, 256, 256, 4, (16, 128))
                for k in ("fwd", "da", "dw")] == [(16, 128)] * 3
        # the dw kernel's need is stated without weight buffers
        assert (sg._vmem_bytes("da", 256, 256, 4096, 2)
                - sg._vmem_bytes("dw", 256, 256, 4096, 2)
                >= 4 * 4096 * 256 * 2)

    def test_sweep_compiles_a_candidate_once_and_uses_both_grads(
            self, monkeypatch):
        # what the sweep times is the kernels, not a compile a call: the
        # first run of a candidate traces, a second does not; candidates
        # that shrink to the same blocks are timed once; each kernel is
        # swept under its own key, beside the winners so far
        from paddle_tpu.kernels import autotune
        traced, swept = [], []
        for name in ("_fwd_kernel", "_bwd_dw_kernel"):
            monkeypatch.setattr(sg, name, lambda *r, _real=getattr(
                sg, name), **k: (traced.append(1), _real(*r, **k))[1])

        def fake(key, candidates, make_fn, default, iters, sweep):
            fns = [f for f in map(make_fn, candidates) if f is not None]
            before = len(traced)
            float(fns[0]())
            first = len(traced) - before
            float(fns[0]())
            swept.append((key.split(":")[0], len(fns), first,
                          len(traced) - before))
            return default

        monkeypatch.setattr(autotune, "autotune", fake)
        got = sg.sweep_block_sizes((32, 128), (128, 256), jnp.float32,
                                   iters=1)
        assert got == ((32, 128), (32, 128), (32, 256))
        # M = 128, 32 rows: every fwd / da candidate is the one (32, 128)
        # block; dw's are (32, 256), and the (32, 128) that none asks for
        assert [(k, n) for k, n, _, _ in swept] == [
            ("swiglu", 1), ("swiglu_bwd_da", 1), ("swiglu_bwd_dw", 1)]
        # the forward and swiglu_bwd_dw are both in what is timed
        assert all(first >= 2 and total == first
                   for _, _, first, total in swept)

    def test_mp_split_under_a_sharded_step_matches_unsharded(self):
        # w_gate_up is [H, gate | up]: under shard_kernel it is split on
        # M inside each half, never across the halves
        from jax.sharding import Mesh
        from paddle_tpu.distributed.sharding import kernel_mesh_guard
        from paddle_tpu.models.llama import _swiglu
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("sharding", "mp"))
        a = _rand((4, 8, 128), jnp.float32)
        w = _rand((128, 512), jnp.float32, seed=1) * 0.05

        def loss(fn):
            return lambda a_, w_: jnp.sum(fn(a_, w_) ** 2)

        def sharded(a_, w_):
            with kernel_mesh_guard(mesh):
                return _swiglu(a_, w_)

        want = jax.value_and_grad(loss(sg.swiglu), argnums=(0, 1))(a, w)
        got = jax.jit(jax.value_and_grad(loss(sharded), argnums=(0, 1)))(
            a, w)
        for g, r in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-5, atol=1e-5)

    def test_supported_gates(self):
        assert sg.supported((8, 256), (256, 512))
        assert not sg.supported((8, 256), (256, 400))   # M % 128
        assert not sg.supported((8, 200), (200, 512))   # H % 128
        assert not sg.supported((8, 128), (256, 512))   # a[-1] != H


# ------------------------------------------------- fused QKV + RoPE

class TestFusedQKVRope:
    def _manual(self, a, w, nh, kvh, d, position_ids=None, seq_len=None):
        qkv = a @ w
        lead = qkv.shape[:-1]
        q = qkv[..., :nh * d].reshape(*lead, nh, d)
        k = qkv[..., nh * d:(nh + kvh) * d].reshape(*lead, kvh, d)
        v = qkv[..., (nh + kvh) * d:].reshape(*lead, kvh, d)
        q, k = rope.apply_rope(q, k, position_ids=position_ids,
                               seq_len=seq_len)
        return q, k, v

    @pytest.mark.parametrize("nh,kvh", [(4, 4), (8, 2)])
    def test_batch_parity_incl_gqa(self, nh, kvh):
        d = 8
        a = _rand((2, 6, 64), jnp.float32)
        w = _rand((64, (nh + 2 * kvh) * d), jnp.float32, seed=1) * 0.1
        got = rope.fused_qkv_rope(a, w, nh, kvh, d)
        want = self._manual(a, w, nh, kvh, d)
        for g, t in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(t))

    def test_packed_rows_with_positions(self):
        nh, kvh, d = 8, 2, 8
        a = _rand((6, 64), jnp.float32)
        w = _rand((64, (nh + 2 * kvh) * d), jnp.float32, seed=1) * 0.1
        pos = jnp.asarray([0, 1, 2, 0, 1, 5])
        got = rope.fused_qkv_rope(a, w, nh, kvh, d, position_ids=pos,
                                  seq_len=16)
        want = self._manual(a[None], w, nh, kvh, d,
                            position_ids=pos[None], seq_len=16)
        want = tuple(t[0] for t in want)
        for g, t in zip(got, want):
            assert g.shape == t.shape
            assert np.array_equal(np.asarray(g), np.asarray(t))


# ------------------------- the model against a plain float32 decoder

def _tiny_model(seed=0, **kw):
    paddle.seed(seed)
    cfg = llama_tiny(dtype="float32", **kw)
    return LlamaForCausalLM(cfg)


def _plain_logits(state, cfg, ids):
    """Pre-norm decoder from the architecture's equations in jax.numpy:
    no kernel, no cache, nothing of models/llama.py but the state dict's
    layout (qkv_proj = q | k | v columns, gate_up_proj = gate | up)."""
    nh, kvh, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    m, eps = cfg.intermediate_size, cfg.rms_norm_eps
    T = ids.shape[1]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2) / d))
    f = jnp.arange(T)[:, None] * inv[None]
    cos, sin = jnp.cos(f)[None, :, None], jnp.sin(f)[None, :, None]

    def rope(x):
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    x = state["model.embed_tokens"][ids]
    for i in range(cfg.num_hidden_layers):
        w = {k[len(f"model.layers.{i}."):]: v for k, v in state.items()
             if k.startswith(f"model.layers.{i}.")}
        qkv = rms(x, w["input_layernorm.weight"]) @ w["self_attn.qkv_proj"]
        q = rope(qkv[..., :nh * d].reshape(-1, T, nh, d))
        k = rope(qkv[..., nh * d:(nh + kvh) * d].reshape(-1, T, kvh, d))
        v = qkv[..., (nh + kvh) * d:].reshape(-1, T, kvh, d)
        k, v = (jnp.repeat(t, nh // kvh, axis=2) for t in (k, v))
        s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
        x = x + o.reshape(-1, T, nh * d) @ w["self_attn.o_proj"]
        gu = rms(x, w["post_attention_layernorm.weight"]) \
            @ w["mlp.gate_up_proj"]
        x = x + (jax.nn.silu(gu[..., :m]) * gu[..., m:]) @ w["mlp.down_proj"]
    return rms(x, state["model.norm.weight"]) @ state["lm_head"]


def _plain_loss(state, cfg, ids):
    lg = _plain_logits(state, cfg, ids)[:, :-1]
    tgt = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - tgt)


class TestModelAgainstPlainDecoder:
    def test_train_loss_and_grads_match_plain_decoder(self):
        """Loss and EVERY gradient of llama_tiny (GQA 4/2) against
        autodiff of the plain decoder."""
        m = _tiny_model(num_key_value_heads=2)
        ids = np.random.RandomState(3).randint(0, 1024, (2, 16))
        t = paddle.to_tensor(ids.astype(np.int64))
        loss = m.loss(t, t)
        loss.backward()
        state = {k: p.data for k, p in m.state_dict().items()}
        ref, g_ref = jax.jit(jax.value_and_grad(
            lambda s, i: _plain_loss(s, m.cfg, i)))(state, ids)
        np.testing.assert_allclose(float(loss.numpy()), float(ref),
                                   rtol=1e-5)
        for k, p in m.state_dict().items():
            assert p.grad is not None, k
            np.testing.assert_allclose(
                np.asarray(p.grad.data), np.asarray(g_ref[k]), rtol=2e-3,
                atol=1e-5 * float(jnp.abs(g_ref[k]).max()) + 1e-8,
                err_msg=k)

    def test_greedy_generate_matches_plain_decoder(self):
        """generate() (prefill + decode scan over the KV cache) against
        greedy decoding without a cache through the plain decoder."""
        m = _tiny_model(num_key_value_heads=2)
        prompt = np.random.RandomState(5).randint(0, 1024, (2, 8))
        got = np.asarray(m.generate(
            paddle.to_tensor(prompt.astype(np.int64)),
            max_new_tokens=6).data)
        state = {k: p.data for k, p in m.state_dict().items()}
        # one program for every length: the mask is causal, so what
        # stands after position n - 1 does not reach its logits
        plain = jax.jit(lambda s, i: _plain_logits(s, m.cfg, i))
        ids = np.zeros((2, 8 + 6), np.int64)
        ids[:, :8] = prompt
        for n in range(8, 8 + 6):
            ids[:, n] = np.argmax(plain(state, ids)[:, n - 1], -1)
        assert np.array_equal(got, ids[:, 8:])

    def test_layout_options_are_refused_by_name(self):
        """The stored layout is not an option: the retired fields are
        rejected, not silently ignored."""
        with pytest.raises(TypeError, match="fuse_mlp"):
            llama.LlamaConfig(fuse_mlp=False)
        with pytest.raises(TypeError, match="fuse_attention_qkv"):
            llama_tiny(fuse_attention_qkv=False)

    def test_serving_rms_is_the_kernel_module(self, monkeypatch):
        """The serving blocks' RMSNorm is kernels/rms_norm.rms_norm: no
        second implementation in models/llama.py."""
        from paddle_tpu.kernels import rms_norm as rn
        calls = []
        real = rn.rms_norm

        def spy(x, w, eps=1e-6):
            calls.append(x.shape)
            return real(x, w, eps)

        monkeypatch.setattr(rn, "rms_norm", spy)
        m = _tiny_model()
        m.generate(paddle.to_tensor(np.zeros((1, 4), np.int64)),
                   max_new_tokens=2)
        # per trace: two norms a layer (scanned: traced once) + the final
        assert len(calls) >= 6 and not hasattr(llama, "_rms")


# ------------------------------- remat-policy knob + donation audit

class TestRematPolicyAndDonation:
    def test_resolve_remat_policy(self):
        resolve = paddle.jit.resolve_remat_policy
        assert resolve(None) is None
        assert callable(resolve("save_matmul_outputs"))
        assert callable(resolve("nothing"))
        assert callable(resolve("dots"))
        sentinel = lambda *a, **k: True  # noqa: E731
        assert resolve(sentinel) is sentinel
        with pytest.raises(ValueError, match="remat_policy"):
            resolve("save_everything_twice")

    def test_policies_bitwise_and_donation_clean(self):
        """Remat policies move memory, not values: the same losses.
        Donation audit: the old param buffers are actually consumed
        (donated) and XLA emits no donation-ignored warnings."""
        rng = np.random.RandomState(11)
        ids = paddle.to_tensor(
            rng.randint(0, 1024, (2, 16)).astype(np.int64))
        losses = {}
        for policy in ("save_matmul_outputs", "nothing"):
            m = _tiny_model()
            o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
            ts = paddle.jit.TrainStep(m, o, lambda i, l: m.loss(i, l),
                                      remat_policy=policy)
            with warnings.catch_warnings(record=True) as rec:
                warnings.simplefilter("always")
                first = m.state_dict()
                old = {k: t.data for k, t in first.items()}
                run = [float(ts(ids, ids).numpy()) for _ in range(3)]
            losses[policy] = run
            donation_msgs = [str(w.message) for w in rec
                             if "donat" in str(w.message).lower()]
            assert not donation_msgs, donation_msgs
            deleted = [old[k].is_deleted() for k in old]
            assert any(deleted), \
                "no param buffer was donated into the compiled step"
        # the forward is one program under either policy: step 0 is
        # bitwise. The backward is not — what a policy does not save is
        # recomputed INSIDE the backward, where XLA (jaxlib 0.9) fuses
        # the recomputed chain with its consumer and rounds one f32 ulp
        # apart from the saved value; from the first update on the
        # losses agree to rounding (1.7e-7 relative seen), not bitwise.
        a, b = losses["save_matmul_outputs"], losses["nothing"]
        assert a[0] == b[0]
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)

    def test_checkpoint_name_stamps_exist(self):
        # the ONE tuple the default policy is built from: the dense
        # decoder's four matmul outputs and the attention kernel's
        # residuals (tests/test_kept_residuals.py)
        assert paddle.jit.KEPT_CHECKPOINT_NAMES == (
            "llama_qkv", "llama_attn_o", "llama_swiglu",
            "llama_mlp_down", "splash_residuals")
        assert not hasattr(llama, "MATMUL_CHECKPOINT_NAMES")
