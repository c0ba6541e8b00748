"""Fused SwiGLU MLP prologue (ref: phi/kernels/fusion/gpu/
fused_gate_attention + fused_bias_act; TPU-native blockwise Pallas
kernel with the silu(g)*u epilogue fused into the gate/up matmul).

A plain MLP materializes `gu = a @ w_gate_up` — a [T, 2M] tensor
(4H-wide at llama ratios) that exists only to be split, activated and
multiplied — an HBM round trip XLA does not reliably elide across the
autograd seam. Here the gate/up products are streamed block-by-block
through VMEM: each (row-block, column-block) grid step computes
g = a·wg and u = a·wu for one [bt, bm] tile in f32, applies
silu(g) * u in-register, and writes only the [T, M] activation out.
The backward is two Pallas kernels with opposite accumulation orders.
`swiglu_bwd_da` recomputes the g/u tile from (a, w) once, forms the
gate/up cotangents dg/du in f32, accumulates da += dg·wgᵀ + du·wuᵀ over
the column blocks, and writes the dg | du tiles, in the activation's
dtype and gate columns first like w_gate_up, to a [T, 2M] buffer in HBM.
`swiglu_bwd_dw` is then a plain transposed matmul, dw_gate_up = aᵀ·dgu,
summed in f32 over the row blocks and written as one [H, 2M] array: no
weight operand, no second recomputation of g and u (which cost as many
MXU flops as the gradient itself). The [T, 2M] cotangent lives only
inside one layer's backward. The forward's gu is still not saved: that
would drop the remaining recomputation too, at the price of [T, 2M]
more bytes a layer kept between forward and backward.

Off the TPU, and for shapes the kernels do not tile (H or M not a
multiple of 128), the jnp fallback computes the plain expression
`silu(gu[..., :M]) * gu[..., M:]`, and the fallback backward is jax.vjp
of that expression: it is also the reference of the interpret-mode
tests. Tests flip `_FORCE_PALLAS` to drive the Pallas path through the
interpreter on CPU.

Each kernel has its own block sizes (`_blocks`): from kernels/autotune.py
(keys "swiglu" for the forward, "swiglu_bwd_da", "swiglu_bwd_dw";
quantized M size classes) where a sweep has left a winner
(`sweep_block_sizes`), else from the shapes.
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


# swiglu_bwd_da holds a (rows, H) operand, its (rows, H) output and an
# f32 accumulator of that shape at once, swiglu_bwd_dw an (H, cols)
# output with its accumulator: at H=4096 no (8, 128)-tiled block pair
# fits the 16 MiB a kernel is scoped to by default, so these kernels ask
# for more of v5e's 128 MiB VMEM and size blocks to 3/4 of it
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


# autotune key and the (rows, columns) each kernel wants before they are
# made to tile and to fit. The backward's were timed on a v5e at the
# benchmark's shapes, (T, H, M) = (4096, 4096, 11008) and (4096, 4096,
# 5504) in bf16 (PERF.md section 6, PR 26): the defaults are the winners
_KERNELS = {"fwd": ("swiglu", (256, 512)),
            "da": ("swiglu_bwd_da", (512, 512)),
            "dw": ("swiglu_bwd_dw", (1024, 512))}


def _vmem_bytes(kernel: str, bt: int, bc: int, H: int, itemsize: int) -> int:
    """What a kernel keeps in VMEM for its (row, column) block pair:
    operands and outputs double-buffered, f32 accumulators. "da" holds
    a (bt, H) operand, output and accumulator, both weight blocks, the
    four f32 g/u/dg/du tiles and the two-slot dg | du buffer its copies
    to HBM read; the forward reads the same blocks and holds less.
    "dw" is a matmul and holds no weights: an (H, bc) output and
    accumulator, and the (bt, H) and (bt, bc) blocks it streams."""
    if kernel == "dw":
        return (H * bc * (2 * itemsize + 4)      # dw, accumulator
                + 2 * bt * (H + bc) * itemsize)  # a, dgu
    return (bt * H * (4 * itemsize + 4)          # a, da, accumulator
            + 4 * H * bc * itemsize              # wg + wu
            + bt * bc * (6 * itemsize + 16))     # do, dg | du slots, tiles


def _blocks(kernel: str, T: int, H: int, M: int, itemsize: int, blocks=None):
    """(row-block, column-block) per grid step of `kernel`: the explicit
    override (sweeps), else the autotune winner for this size class,
    else the kernel's default — then made to tile and to fit.

    The forward and swiglu_bwd_da read a gate and an up block of
    w_gate_up: their column block is a multiple of 128 that divides M,
    so the up half starts on a block boundary (M % 128 == 0 is what
    supported() admits: 256 for M=2816 and 11008, 128 for M=5504), and
    a block that does not fit gives up columns first (the weights are
    re-read once a row block). swiglu_bwd_dw runs over the flat 2M
    columns of dgu and re-reads all of `a` once a column block: bn
    flops a byte, against the ~240 the chip can feed, so it wants 512
    columns where the others stop at 256 or 128, and gives up rows
    first. Its bn need not divide 2M: a last partial block multiplies
    columns that are dropped on write. The row block is a multiple of
    16, or all T rows; rows that do not fill the last block are padded
    on read and masked where they would be summed."""
    key, default = _KERNELS[kernel]
    if blocks is not None and isinstance(blocks[0], (tuple, list)):
        blocks = blocks[list(_KERNELS).index(kernel)]
    if blocks is None:
        from . import autotune
        hit = autotune.lookup(autotune.cache_key(key, M=_size_class(M)))
        if hit and isinstance(hit, (list, tuple)) and len(hit) == 2:
            blocks = hit
    bt, bc = blocks if blocks is not None else default
    bts = [max(SUBLANES, int(bt) // SUBLANES * SUBLANES)]
    while bts[-1] > SUBLANES:
        bts.append(max(SUBLANES, bts[-1] // 2 // SUBLANES * SUBLANES))
    bc = max(LANES, int(bc) // LANES * LANES)
    if kernel == "dw":
        pairs = [(bt, b) for b in range(min(bc, 2 * M), 0, -LANES)
                 for bt in bts]
    else:
        pairs = [(bt, b) for bt in bts
                 for b in range(min(bc, M), 0, -LANES) if M % b == 0]
    for bt, bc in pairs:
        if _vmem_bytes(kernel, bt, bc, H, itemsize) <= _VMEM_BLOCK_BUDGET:
            return (T if T <= bt else bt), bc
    raise ValueError(
        f"swiglu: no (rows, columns) block of H={H}, M={M}, "
        f"itemsize={itemsize} fits {_VMEM_BLOCK_BUDGET} bytes of VMEM "
        f"in the {kernel} kernel")


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
    """The plain expression: what runs where the kernels do not, and what
    the interpret-mode tests compare them with."""
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


def _bwd_da_kernel(a_ref, wg_ref, wu_ref, do_ref, da_ref, dgu_ref, acc_ref,
                   tile_ref, sem, *, nm, M):
    i, j = pl.program_id(0), pl.program_id(1)
    bt, bm = do_ref.shape

    def writes(j_):
        """The two copies that put column block j_'s dg | du tiles where
        w_gate_up's columns put gate | up: one array takes two blocks a
        grid step, which no output BlockSpec can say, so dgu stays in
        HBM and the tiles leave through a two-slot VMEM buffer."""
        slot = j_ % 2
        return [pltpu.make_async_copy(
            tile_ref.at[slot, half],
            dgu_ref.at[pl.ds(i * bt, bt), pl.ds(half * M + j_ * bm, bm)],
            sem.at[slot, half]) for half in (0, 1)]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j >= 2)
    def _slot_is_free():
        for w in writes(j - 2):
            w.wait()

    dg, du = _dgu_tile(a_ref, wg_ref, wu_ref, do_ref)
    tile_ref[j % 2, 0] = dg.astype(tile_ref.dtype)
    tile_ref[j % 2, 1] = du.astype(tile_ref.dtype)
    for w in writes(j):
        w.start()
    dims = (((1,), (1,)), ((), ()))          # contract the M-block axis
    acc_ref[...] += (
        jax.lax.dot_general(dg, wg_ref[...], dims,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(du, wu_ref[...], dims,
                              preferred_element_type=jnp.float32))

    @pl.when(j == nm - 1)
    def _emit():
        da_ref[...] = acc_ref[...].astype(da_ref.dtype)
        # a row block leaves nothing in flight behind it, so the row
        # axis stays free to be split over cores
        for j_ in range(max(nm - 2, 0), nm):
            for w in writes(j_):
                w.wait()


def _bwd_dw_kernel(a_ref, dgu_ref, dw_ref, acc_ref, *, nt, rows):
    """dw_gate_up = a^T . dgu, one [H, bn] column block of the flat 2M
    axis a grid row, summed over the row blocks."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a, dgu = a_ref[...], dgu_ref[...]
    bt = a.shape[0]
    if rows % bt:
        # the last row block reads past T: what it read there is
        # arbitrary and must not reach the sum over rows
        live = (t * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
                < rows)
        a = jnp.where(live, a, jnp.zeros_like(a))
        dgu = jnp.where(live, dgu, jnp.zeros_like(dgu))
    dims = (((0,), (0,)), ((), ()))          # contract the row-block axis
    acc_ref[...] += jax.lax.dot_general(
        a, dgu, dims, preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _emit():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _fwd_impl(a, w_gate_up, use_pallas, blocks):
    if not _route(a.shape, w_gate_up.shape, use_pallas):
        return _ref(a, w_gate_up)
    orig_shape = a.shape
    H = orig_shape[-1]
    M = w_gate_up.shape[-1] // 2
    af = a.reshape(-1, H)
    T = af.shape[0]
    bt, bm = _blocks("fwd", T, H, M, a.dtype.itemsize, blocks)
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
        # autodiff of the plain expression
        _, vjp = jax.vjp(_ref, a, w_gate_up)
        return vjp(g)
    orig_shape = a.shape
    H = orig_shape[-1]
    M = w_gate_up.shape[-1] // 2
    af = a.reshape(-1, H)
    gf = g.reshape(-1, M)
    T = af.shape[0]
    itemsize = a.dtype.itemsize
    bt, bm = _blocks("da", T, H, M, itemsize, blocks)
    nt, nm = pl.cdiv(T, bt), M // bm
    interpret = not _on_tpu()
    # dgu's rows are whole row blocks: the copies that write it are not
    # clipped to T as an output block's would be
    da, dgu = pl.pallas_call(
        functools.partial(_bwd_da_kernel, nm=nm, M=M),
        out_shape=(jax.ShapeDtypeStruct((T, H), a.dtype),
                   jax.ShapeDtypeStruct((nt * bt, 2 * M), a.dtype)),
        grid=(nt, nm),
        in_specs=[
            pl.BlockSpec((bt, H), lambda i, j: (i, 0)),
            pl.BlockSpec((H, bm), lambda i, j: (0, j)),
            pl.BlockSpec((H, bm), lambda i, j, nm=nm: (0, j + nm)),
            pl.BlockSpec((bt, bm), lambda i, j: (i, j)),
        ],
        out_specs=(pl.BlockSpec((bt, H), lambda i, j: (i, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.float32),
                        pltpu.VMEM((2, 2, bt, bm), a.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
        compiler_params=_compiler_params(interpret, "parallel", "arbitrary"),
        interpret=interpret,
        name="swiglu_bwd_da",
    )(af, w_gate_up, w_gate_up, gf)
    bt, bn = _blocks("dw", T, H, M, itemsize, blocks)
    nt = pl.cdiv(T, bt)
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, nt=nt, rows=T),
        out_shape=jax.ShapeDtypeStruct((H, 2 * M), w_gate_up.dtype),
        grid=(pl.cdiv(2 * M, bn), nt),
        in_specs=[
            pl.BlockSpec((bt, H), lambda n, t: (t, 0)),
            pl.BlockSpec((bt, bn), lambda n, t: (t, n)),
        ],
        out_specs=pl.BlockSpec((H, bn), lambda n, t: (0, n)),
        scratch_shapes=[pltpu.VMEM((H, bn), jnp.float32)],
        compiler_params=_compiler_params(interpret, "parallel", "arbitrary"),
        interpret=interpret,
        name="swiglu_bwd_dw",
    )(af, dgu)
    # dw is a buffer of its own. Left to itself XLA fuses the call with
    # the write of dw into a scanned stack's gradient and scopes that
    # fusion to its default 16 MiB of VMEM, whatever the call asks for:
    # the compiler then refuses the step (described v5e, PERF.md PR 26)
    return da.reshape(orig_shape), jax.lax.optimization_barrier(dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def swiglu(a, w_gate_up, use_pallas=None, blocks=None):
    """a: [..., H]; w_gate_up: [H, 2M] (gate columns first). Returns
    silu(a @ w_gate) * (a @ w_up): [..., M]. The down projection stays
    outside — its input is the kernel's output, already in HBM.

    use_pallas: None = auto (real TPU + aligned, or _FORCE_PALLAS via
    the interpreter), True/False forces the route; blocks overrides the
    autotuned blocks (the sweep's candidate lever): one (rows, columns)
    for every kernel, or one each for swiglu_fwd, swiglu_bwd_da and
    swiglu_bwd_dw."""
    return _fwd_impl(a, w_gate_up, use_pallas, blocks)


def _swiglu_fwd(a, w_gate_up, use_pallas, blocks):
    return _fwd_impl(a, w_gate_up, use_pallas, blocks), (a, w_gate_up)


def _swiglu_bwd(use_pallas, blocks, res, g):
    a, w_gate_up = res
    return _bwd_impl(a, w_gate_up, g, use_pallas, blocks)


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def sweep_block_sizes(a_shape, w_shape, dtype=jnp.bfloat16, iters=8,
                      sweep=None):
    """Register/refresh each kernel's (row, column) block winner for one
    size class with kernels/autotune.py (PADDLE_AUTOTUNE=1 or
    sweep=True; cached winners are consulted by _blocks
    unconditionally). Kernel after kernel, beside the winners so far,
    timing the forward and the backward together: the value and both
    gradients are used, or the compiler drops the kernel that makes
    the unused one. Returns the three winners, forward first."""
    from . import autotune
    H, M2 = int(w_shape[0]), int(w_shape[1])
    M = M2 // 2
    rows = 1
    for s in a_shape[:-1]:
        rows *= int(s)
    itemsize = jnp.dtype(dtype).itemsize
    best = {k: _blocks(k, rows, H, M, itemsize) for k in _KERNELS}

    def make_fn(kernel, cand):
        blocks = tuple(tuple(cand if k == kernel else best[k])
                       for k in _KERNELS)
        rng = jax.random.PRNGKey(0)
        a = jax.random.normal(rng, (rows, H), jnp.float32).astype(dtype)
        w = jax.random.normal(rng, (H, M2), jnp.float32).astype(dtype)

        def loss(a_, w_):
            return jnp.sum(swiglu(a_, w_, use_pallas=True,
                                  blocks=blocks).astype(jnp.float32))

        @jax.jit                  # once a candidate: run() only calls it
        def loop(a_, w_):
            def body(c, _):
                out, (da, dw) = jax.value_and_grad(loss, argnums=(0, 1))(
                    a_ * (1 + 0 * c).astype(dtype), w_)
                used = out + (da[0, 0] + dw[0, 0]).astype(jnp.float32)
                return c + 0 * used, None
            return jax.lax.scan(body, jnp.float32(0), None, length=iters)[0]

        return lambda: loop(a, w)

    candidates = {
        "fwd": [(128, 512), (256, 128), (256, 256), (256, 512), (512, 256)],
        "da": [(256, 128), (256, 256), (256, 512), (512, 128), (512, 256),
               (512, 512)],
        "dw": [(256, 256), (256, 512), (512, 256), (512, 512), (512, 1024),
               (1024, 256), (1024, 512), (1024, 1024)]}
    for kernel, (key, _) in _KERNELS.items():
        # as they would run: candidates that shrink to one block are one
        fitted = dict.fromkeys(_blocks(kernel, rows, H, M, itemsize, c)
                               for c in candidates[kernel])
        best[kernel] = autotune.autotune(
            autotune.cache_key(key, M=_size_class(M)), list(fitted),
            functools.partial(make_fn, kernel), default=best[kernel],
            iters=iters, sweep=sweep)
    return tuple(tuple(best[k]) for k in _KERNELS)
