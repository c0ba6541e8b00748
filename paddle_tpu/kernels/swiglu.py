"""Fused SwiGLU MLP prologue (ref: phi/kernels/fusion/gpu/
fused_gate_attention + fused_bias_act; TPU-native blockwise Pallas
kernel with the silu(g)*u epilogue fused into the gate/up matmul).

The unfused MLP materializes `gu = a @ w_gate_up` — a [T, 2M] tensor
(4H-wide at llama ratios) that exists only to be split, activated and
multiplied — an HBM round trip XLA does not reliably elide across the
autograd seam. Here the gate/up products are streamed block-by-block
through VMEM: each (row-block, column-block) grid step computes
g = a·wg and u = a·wu for one [bt, bm] tile in f32, applies
silu(g) * u in-register, and writes only the [T, M] activation out.
The backward is two Pallas kernels with opposite accumulation orders —
da accumulates over column blocks, dw_gate_up over row blocks — each
recomputing its g/u tile from (a, w) so the [T, 2M] intermediate never
hits HBM in either direction.

The jnp fallback computes the exact unfused expression
`silu(gu[..., :M]) * gu[..., M:]`, and the fallback backward is
jax.vjp of that expression, so FLAGS_fused_transformer=0 parity and
interpret-mode tests share one reference. Tests flip `_FORCE_PALLAS`
to drive the Pallas path through the interpreter on CPU.

Block sizes come from kernels/autotune.py (key "swiglu", quantized
H/M size classes) — sweep via `sweep_block_sizes`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ._tpu import LANES, SUBLANES
from ._tpu import on_tpu as _on_tpu

__all__ = ["swiglu", "supported", "sweep_block_sizes"]

# tests flip this to exercise the Pallas path through the interpreter on
# CPU (interpret mode is orders of magnitude slower than the fallback)
_FORCE_PALLAS = False


# The backward holds a (rows, H) operand, its (rows, H) output and an f32
# accumulator of that shape at once, and the weight-gradient kernel two
# (H, cols) outputs with their accumulators: at H=4096 no (8, 128)-tiled
# block pair fits the 16 MiB a kernel is scoped to by default, so these
# kernels ask for more of v5e's 128 MiB VMEM and size blocks to 3/4 of it
_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BLOCK_BUDGET = 48 * 1024 * 1024


def supported(a_shape, w_shape) -> bool:
    """a: [..., H]; w_gate_up: [H, 2M] — Mosaic-alignment gate for the
    compiled route (the fallback handles everything)."""
    H, M2 = int(w_shape[0]), int(w_shape[1])
    M = M2 // 2
    return (int(a_shape[-1]) == H and M2 == 2 * M
            and H % 128 == 0 and M % 128 == 0)


def _size_class(n: int) -> int:
    c = 128
    while c < n:
        c *= 2
    return c


def _vmem_bytes(bt: int, bm: int, H: int, itemsize: int) -> int:
    """What the larger of the two backward kernels keeps in VMEM for a
    (bt, bm) block pair: operands and outputs double-buffered, f32
    accumulators, the four f32 g/u/dg/du tiles."""
    w = 4 * H * bm * itemsize                    # wg + wu, two buffers
    tiles = bt * bm * (2 * itemsize + 16)        # do + g/u/dg/du
    da = bt * H * (4 * itemsize + 4) + w + tiles
    dw = H * bm * (4 * itemsize + 8) + w + 2 * bt * H * itemsize + tiles
    return max(da, dw)


def _blocks(T: int, H: int, M: int, itemsize: int, blocks=None):
    """(row-block, column-block) per grid step: explicit override
    (sweeps), else the autotune winner for this size class, else
    (256, 512) — then made to tile and to fit. The column block is a
    multiple of 128 that divides M, so the up half of w_gate_up starts
    on a block boundary (M % 128 == 0 is what supported() admits: 256
    for M=2816 and 11008, 128 for M=5504). The row block is a multiple
    of 16, or all T rows; rows that do not fill the last block are
    padded on read and masked where they would be summed."""
    if blocks is None:
        from . import autotune
        hit = autotune.lookup(autotune.cache_key(
            "swiglu", M=_size_class(M)))
        if hit and isinstance(hit, (list, tuple)) and len(hit) == 2:
            blocks = (int(hit[0]), int(hit[1]))
    if blocks is None:
        blocks = (256, 512)
    want_bt = max(SUBLANES, int(blocks[0]) // SUBLANES * SUBLANES)
    want_bm = max(LANES, int(blocks[1]) // LANES * LANES)
    bms = [b for b in range(min(want_bm, M), 0, -LANES) if M % b == 0]
    bt = want_bt
    while True:
        for bm in bms:
            if _vmem_bytes(bt, bm, H, itemsize) <= _VMEM_BLOCK_BUDGET:
                return (T if T <= bt else bt), bm
        if bt == SUBLANES:
            raise ValueError(
                f"swiglu: no (rows, columns) block of H={H}, M={M}, "
                f"itemsize={itemsize} fits {_VMEM_BLOCK_BUDGET} bytes of "
                f"VMEM")
        bt = max(SUBLANES, bt // 2 // SUBLANES * SUBLANES)


def _compiler_params(interpret, *semantics):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _route(a_shape, w_shape, use_pallas):
    if use_pallas is None:
        return (supported(a_shape, w_shape)
                and (_on_tpu() or _FORCE_PALLAS))
    if use_pallas and not supported(a_shape, w_shape):
        # an EXPLICIT True must not silently time/run the fallback
        raise ValueError(
            f"swiglu: use_pallas=True but shapes are not Mosaic-aligned "
            f"(a {tuple(a_shape)}, w_gate_up {tuple(w_shape)}: need "
            f"a[-1] == H, H % 128 == 0, (2M)/2 % 128 == 0)")
    return use_pallas


def _ref(a, w_gate_up):
    """The exact unfused expression (LlamaMLP's fused-weight path)."""
    m = w_gate_up.shape[-1] // 2
    gu = a @ w_gate_up
    return jax.nn.silu(gu[..., :m]) * gu[..., m:]


def _gu_tile(a_ref, wg_ref, wu_ref):
    a = a_ref[...]
    g = jnp.dot(a, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(a, wu_ref[...], preferred_element_type=jnp.float32)
    return g, u


def _fwd_kernel(a_ref, wg_ref, wu_ref, o_ref):
    g, u = _gu_tile(a_ref, wg_ref, wu_ref)
    o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)


def _dgu_tile(a_ref, wg_ref, wu_ref, do_ref):
    """Recompute the g/u tile and turn the output cotangent into the
    gate/up cotangents (silu'(g) = s + g*s*(1-s), s = sigmoid(g))."""
    g, u = _gu_tile(a_ref, wg_ref, wu_ref)
    do = do_ref[...].astype(jnp.float32)
    s = jax.nn.sigmoid(g)
    dg = do * u * (s + g * s * (1.0 - s))
    du = do * (g * s)
    return dg, du


def _bwd_da_kernel(a_ref, wg_ref, wu_ref, do_ref, da_ref, acc_ref, *, nm):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dg, du = _dgu_tile(a_ref, wg_ref, wu_ref, do_ref)
    dims = (((1,), (1,)), ((), ()))          # contract the M-block axis
    acc_ref[...] += (
        jax.lax.dot_general(dg, wg_ref[...], dims,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(du, wu_ref[...], dims,
                              preferred_element_type=jnp.float32))

    @pl.when(j == nm - 1)
    def _emit():
        da_ref[...] = acc_ref[...].astype(da_ref.dtype)


def _bwd_dw_kernel(a_ref, wg_ref, wu_ref, do_ref, dwg_ref, dwu_ref,
                   accg_ref, accu_ref, *, nt, rows):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    dg, du = _dgu_tile(a_ref, wg_ref, wu_ref, do_ref)
    a = a_ref[...]
    bt = a.shape[0]
    if rows % bt:
        # the last row block reads past T: what it read there is
        # arbitrary and must not reach the sum over rows
        live = (t * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
                < rows)
        a = jnp.where(live, a, jnp.zeros_like(a))
        dg = jnp.where(live, dg, 0.0)
        du = jnp.where(live, du, 0.0)
    dims = (((0,), (0,)), ((), ()))          # contract the row-block axis
    accg_ref[...] += jax.lax.dot_general(
        a, dg, dims, preferred_element_type=jnp.float32)
    accu_ref[...] += jax.lax.dot_general(
        a, du, dims, preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _emit():
        dwg_ref[...] = accg_ref[...].astype(dwg_ref.dtype)
        dwu_ref[...] = accu_ref[...].astype(dwu_ref.dtype)


def _fwd_impl(a, w_gate_up, use_pallas, blocks):
    if not _route(a.shape, w_gate_up.shape, use_pallas):
        return _ref(a, w_gate_up)
    orig_shape = a.shape
    H = orig_shape[-1]
    M = w_gate_up.shape[-1] // 2
    af = a.reshape(-1, H)
    T = af.shape[0]
    bt, bm = _blocks(T, H, M, a.dtype.itemsize, blocks)
    nm = M // bm
    interpret = not _on_tpu()
    out = pl.pallas_call(
        _fwd_kernel,
        out_shape=jax.ShapeDtypeStruct((T, M), a.dtype),
        grid=(pl.cdiv(T, bt), nm),
        in_specs=[
            pl.BlockSpec((bt, H), lambda i, j: (i, 0)),
            pl.BlockSpec((H, bm), lambda i, j: (0, j)),
            pl.BlockSpec((H, bm), lambda i, j, nm=nm: (0, j + nm)),
        ],
        out_specs=pl.BlockSpec((bt, bm), lambda i, j: (i, j)),
        compiler_params=_compiler_params(interpret, "parallel", "parallel"),
        interpret=interpret,
        name="swiglu_fwd",
    )(af, w_gate_up, w_gate_up)
    return out.reshape(orig_shape[:-1] + (M,))


def _bwd_impl(a, w_gate_up, g, use_pallas, blocks):
    if not _route(a.shape, w_gate_up.shape, use_pallas):
        # autodiff of the exact unfused expression — bitwise the
        # FLAGS_fused_transformer=0 tape on CPU
        _, vjp = jax.vjp(_ref, a, w_gate_up)
        return vjp(g)
    orig_shape = a.shape
    H = orig_shape[-1]
    M = w_gate_up.shape[-1] // 2
    af = a.reshape(-1, H)
    gf = g.reshape(-1, M)
    T = af.shape[0]
    bt, bm = _blocks(T, H, M, a.dtype.itemsize, blocks)
    nt, nm = pl.cdiv(T, bt), M // bm
    interpret = not _on_tpu()
    da = pl.pallas_call(
        functools.partial(_bwd_da_kernel, nm=nm),
        out_shape=jax.ShapeDtypeStruct((T, H), a.dtype),
        grid=(nt, nm),
        in_specs=[
            pl.BlockSpec((bt, H), lambda i, j: (i, 0)),
            pl.BlockSpec((H, bm), lambda i, j: (0, j)),
            pl.BlockSpec((H, bm), lambda i, j, nm=nm: (0, j + nm)),
            pl.BlockSpec((bt, bm), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bt, H), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.float32)],
        compiler_params=_compiler_params(interpret, "parallel", "arbitrary"),
        interpret=interpret,
        name="swiglu_bwd_da",
    )(af, w_gate_up, w_gate_up, gf)
    dwg, dwu = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, nt=nt, rows=T),
        out_shape=(jax.ShapeDtypeStruct((H, M), w_gate_up.dtype),
                   jax.ShapeDtypeStruct((H, M), w_gate_up.dtype)),
        grid=(nm, nt),
        in_specs=[
            pl.BlockSpec((bt, H), lambda m, t: (t, 0)),
            pl.BlockSpec((H, bm), lambda m, t: (0, m)),
            pl.BlockSpec((H, bm), lambda m, t, nm=nm: (0, m + nm)),
            pl.BlockSpec((bt, bm), lambda m, t: (t, m)),
        ],
        out_specs=(pl.BlockSpec((H, bm), lambda m, t: (0, m)),
                   pl.BlockSpec((H, bm), lambda m, t: (0, m))),
        scratch_shapes=[pltpu.VMEM((H, bm), jnp.float32),
                        pltpu.VMEM((H, bm), jnp.float32)],
        compiler_params=_compiler_params(interpret, "parallel", "arbitrary"),
        interpret=interpret,
        name="swiglu_bwd_dw",
    )(af, w_gate_up, w_gate_up, gf)
    dw = jnp.concatenate([dwg, dwu], axis=-1)
    return da.reshape(orig_shape), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def swiglu(a, w_gate_up, use_pallas=None, blocks=None):
    """a: [..., H]; w_gate_up: [H, 2M] (gate columns first). Returns
    silu(a @ w_gate) * (a @ w_up): [..., M]. The down projection stays
    outside — its input is the kernel's output, already in HBM.

    use_pallas: None = auto (real TPU + aligned, or _FORCE_PALLAS via
    the interpreter), True/False forces the route; blocks overrides the
    autotuned (row, column) blocks (the sweep's candidate lever)."""
    return _fwd_impl(a, w_gate_up, use_pallas, blocks)


def _swiglu_fwd(a, w_gate_up, use_pallas, blocks):
    return _fwd_impl(a, w_gate_up, use_pallas, blocks), (a, w_gate_up)


def _swiglu_bwd(use_pallas, blocks, res, g):
    a, w_gate_up = res
    return _bwd_impl(a, w_gate_up, g, use_pallas, blocks)


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def sweep_block_sizes(a_shape, w_shape, dtype=jnp.bfloat16, iters=8,
                      sweep=None):
    """Register/refresh the (row, column) block winner for one size
    class with kernels/autotune.py (PADDLE_AUTOTUNE=1 or sweep=True;
    cached winners are consulted by _blocks unconditionally). Times the
    fwd+bwd pair under jax.grad — the backward's two accumulation
    kernels dominate and must share the winner."""
    from . import autotune
    H, M2 = int(w_shape[0]), int(w_shape[1])
    M = M2 // 2
    rows = 1
    for s in a_shape[:-1]:
        rows *= int(s)
    key = autotune.cache_key("swiglu", M=_size_class(M))

    def make_fn(cand):
        bt, bm = cand
        if bt > rows or bm > M:
            return None
        rng = jax.random.PRNGKey(0)
        a = jax.random.normal(rng, (rows, H), jnp.float32).astype(dtype)
        w = jax.random.normal(rng, (H, M2), jnp.float32).astype(dtype)

        def loss(a_, w_):
            return jnp.sum(swiglu(a_, w_, use_pallas=True,
                                  blocks=(bt, bm)).astype(jnp.float32))

        def run():
            def body(c, _):
                da, dw = jax.grad(loss, argnums=(0, 1))(
                    a * (1 + 0 * c).astype(dtype), w)
                return c + 0 * da[0, 0].astype(jnp.float32), None
            return jax.jit(lambda: jax.lax.scan(
                body, jnp.float32(0), None, length=iters))()

        return run

    return autotune.autotune(
        key, [(128, 128), (128, 512), (256, 256), (256, 512), (512, 512)],
        make_fn, default=_blocks(rows, H, M, jnp.dtype(dtype).itemsize),
        iters=iters, sweep=sweep)
