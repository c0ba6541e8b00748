"""Fused residual-add + RMSNorm (ref: phi/kernels/fusion/gpu/
fused_bias_residual_layernorm; TPU-native row-blocked Pallas kernel).

The transformer residual seam `h = x + attn; a = rms_norm(h)` is two
HBM round trips when left to XLA (the custom-vjp boundary around
rms_norm blocks fusion across it). This kernel reads x and the residual
branch once, emits BOTH the summed residual stream h (needed downstream
as the next residual source) and the normalized activation y in one
VMEM pass. The backward is an analytic custom_vjp that recomputes the
rstd from the saved h instead of storing normalized activations:

  h  = x + residual                       (rounded to the stream dtype)
  y  = h * r * w,  r = rsqrt(mean(h^2) + eps)
  dh = gh + r*(gy*w) - h * r^3/H * sum(gy*w*h)    (dx = dresidual = dh)
  dw = sum_rows(gy * h * r)

Off the TPU, and for a hidden size that is not a multiple of 128, the
jnp fallback is the separate `(x + residual)` then rms_norm sequence,
bitwise (same op order, same f32 casts): what LlamaDecoderLayer runs
under sequence parallelism, and the reference of the interpret-mode
parity tests. Tests flip `_FORCE_PALLAS` to drive the Pallas path
through the interpreter on CPU.

Block sizes come from kernels/autotune.py (key "fused_norm", quantized
hidden-size class) — sweep via `sweep_block_sizes`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tpu import on_tpu as _on_tpu
from ._tpu import row_block

__all__ = ["fused_add_rms_norm", "supported", "sweep_block_sizes"]

# tests flip this to exercise the Pallas path through the interpreter on
# CPU (interpret mode is orders of magnitude slower than the fallback)
_FORCE_PALLAS = False


def supported(shape) -> bool:
    """x/residual: [..., H] — Mosaic lane alignment for the compiled
    route (the fallback handles everything)."""
    return int(shape[-1]) % 128 == 0


def _size_class(h: int) -> int:
    """Quantize the hidden size to a power of two so one autotune sweep
    covers one (kernel, size-class, device) point."""
    c = 128
    while c < h:
        c *= 2
    return c


def _block_rows(rows: int, H: int, itemsize: int, block_rows=None) -> int:
    """Rows per grid step: explicit override (sweeps), else the autotune
    winner for this hidden-size class, else 256 — held to what VMEM
    takes: x, residual, y and h blocks, each double-buffered."""
    if block_rows is None:
        from . import autotune
        hit = autotune.lookup(autotune.cache_key("fused_norm",
                                                 H=_size_class(H)))
        if hit:
            block_rows = int(hit[0] if isinstance(hit, (list, tuple))
                             else hit)
    if not block_rows or block_rows <= 0:
        block_rows = 256
    return row_block(rows, 8 * H * itemsize, want=block_rows)


def _route(shape, use_pallas):
    if use_pallas is None:
        return supported(shape) and (_on_tpu() or _FORCE_PALLAS)
    if use_pallas and not supported(shape):
        # an EXPLICIT True must not silently time/run the fallback — a
        # sweep would record noise winners and callers would believe
        # they exercised the compiled route
        raise ValueError(
            f"fused_add_rms_norm: use_pallas=True but shape {tuple(shape)} "
            f"is not Mosaic-aligned (need H % 128 == 0)")
    return use_pallas


def _fwd_kernel(x_ref, r_ref, w_ref, y_ref, h_ref, *, eps):
    # round h to the stream dtype BEFORE normalizing — a separate add
    # then norm sees the rounded residual stream, and parity with that
    # sequence is the contract the interpret tests check
    h = (x_ref[...].astype(jnp.float32)
         + r_ref[...].astype(jnp.float32)).astype(h_ref.dtype)
    h_ref[...] = h
    h32 = h.astype(jnp.float32)
    ms = jnp.mean(h32 * h32, axis=-1, keepdims=True)
    y_ref[...] = (h32 * jax.lax.rsqrt(ms + eps)
                  * w_ref[...].astype(jnp.float32)).astype(y_ref.dtype)


def _fwd_impl(x, residual, weight, eps, use_pallas, block_rows):
    if not _route(x.shape, use_pallas):
        # add (f32 compute, round to stream dtype), then the rms_norm
        # fallback on h
        h = x + residual
        h32 = h.astype(jnp.float32)
        ms = jnp.mean(h32 * h32, axis=-1, keepdims=True)
        y = (h32 * jax.lax.rsqrt(ms + eps)
             * weight.astype(jnp.float32)).astype(x.dtype)
        return y, h
    orig_shape = x.shape
    H = orig_shape[-1]
    xf = x.reshape(-1, H)
    rf = residual.reshape(-1, H)
    rows = xf.shape[0]
    br = _block_rows(rows, H, x.dtype.itemsize, block_rows)
    grid = (pl.cdiv(rows, br),)
    y, h = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        # inside a shard_map that tracks variance (distributed/sharding.
        # shard_kernel) both outputs vary as the rows do; empty outside
        out_shape=(jax.ShapeDtypeStruct(xf.shape, x.dtype,
                                        vma=jax.typeof(xf).vma),) * 2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, H), lambda i: (i, 0)),
            pl.BlockSpec((br, H), lambda i: (i, 0)),
            pl.BlockSpec((H,), lambda i: (0,)),
        ],
        out_specs=(pl.BlockSpec((br, H), lambda i: (i, 0)),
                   pl.BlockSpec((br, H), lambda i: (i, 0))),
        interpret=not _on_tpu(),
        name="fused_add_rms_norm",
    )(xf, rf, weight)
    return y.reshape(orig_shape), h.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_add_rms_norm(x, residual, weight, eps=1e-6, use_pallas=None,
                       block_rows=None):
    """x, residual: [..., H]; weight: [H]. Returns (y, h) with
    h = x + residual and y = rms_norm(h) * weight.

    use_pallas: None = auto (real TPU + aligned, or _FORCE_PALLAS via
    the interpreter), True/False forces the route; block_rows overrides
    the autotuned row block (the sweep's candidate lever)."""
    return _fwd_impl(x, residual, weight, eps, use_pallas, block_rows)


def _fused_fwd(x, residual, weight, eps, use_pallas, block_rows):
    y, h = _fwd_impl(x, residual, weight, eps, use_pallas, block_rows)
    # save h (the rounded residual stream) + weight; rstd is recomputed
    # in the backward — nothing normalized survives the forward
    return (y, h), (h, weight)


def _fused_bwd(eps, use_pallas, block_rows, res, cts):
    h, w = res
    gy, gh = cts
    H = h.shape[-1]
    h32 = h.astype(jnp.float32)
    gy32 = gy.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(h32 * h32, axis=-1, keepdims=True) + eps)
    gw = gy32 * w32
    dnorm = r * gw - h32 * (r ** 3) * jnp.sum(gw * h32, axis=-1,
                                              keepdims=True) / H
    # cotangent accumulation in the stream dtype, matching the tape's
    # add of the rms_norm bwd and the downstream residual cotangent
    dh = dnorm.astype(h.dtype) + gh
    dw = jnp.sum((gy32 * h32 * r).reshape(-1, H), axis=0).astype(w.dtype)
    return dh, dh, dw


fused_add_rms_norm.defvjp(_fused_fwd, _fused_bwd)


def sweep_block_sizes(shape, dtype=jnp.bfloat16, iters=8, sweep=None):
    """Register/refresh the row-block winner for one hidden-size class
    with kernels/autotune.py (PADDLE_AUTOTUNE=1 or sweep=True; cached
    winners are consulted by _block_rows unconditionally)."""
    from . import autotune
    H = int(shape[-1])
    rows = 1
    for s in shape[:-1]:
        rows *= int(s)
    key = autotune.cache_key("fused_norm", H=_size_class(H))

    def make_fn(br):
        if br > rows:
            return None
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (rows, H), jnp.float32).astype(dtype)
        res = jax.random.normal(rng, (rows, H), jnp.float32).astype(dtype)
        w = jnp.ones((H,), jnp.float32)

        def run():
            def body(c, _):
                y, h = fused_add_rms_norm(x + c.astype(dtype), res, w,
                                          use_pallas=True, block_rows=br)
                return c + 0 * y[0, 0].astype(jnp.float32), None
            return jax.jit(lambda: jax.lax.scan(
                body, jnp.float32(0), None, length=iters))()

        return run

    return autotune.autotune(key, [32, 64, 128, 256, 512], make_fn,
                             default=_block_rows(
                                 rows, H, jnp.dtype(dtype).itemsize),
                             iters=iters,
                             sweep=sweep)
