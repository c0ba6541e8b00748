"""Pallas RMSNorm (ref: phi/kernels/fusion/gpu/fused_rms_norm; TPU-native
row-blocked kernel: one VMEM pass, f32 accumulation, bf16 in/out).

XLA usually fuses rms_norm chains already; this kernel exists for the long-
row case (hidden >= 8192) where explicit blocking beats the fusion, and as
the template for further norm kernels. Reverse-mode AD is provided by an
analytic custom_vjp (Pallas calls carry no AD rule of their own):
  y  = x * r * w,  r = rsqrt(mean(x^2) + eps)
  dx = r*(g*w) - x * r^3/H * sum(g*w*x)
  dw = sum_rows(g * x * r)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tpu import on_tpu as _on_tpu
from ._tpu import row_block


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


def _fwd_impl(x, weight, eps):
    if not _on_tpu():
        x32 = x.astype(jnp.float32)
        ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(ms + eps) * weight.astype(jnp.float32)
                ).astype(x.dtype)
    orig_shape = x.shape
    H = orig_shape[-1]
    xf = x.reshape(-1, H)
    rows = xf.shape[0]
    # x and out blocks, each double-buffered
    block_rows = row_block(rows, 4 * H * x.dtype.itemsize)
    grid = (pl.cdiv(rows, block_rows),)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        # inside a shard_map that tracks variance (distributed/sharding.
        # shard_kernel) the output varies as the rows do; empty outside
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype,
                                       vma=jax.typeof(xf).vma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, H), lambda i: (i, 0)),
            pl.BlockSpec((H,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, H), lambda i: (i, 0)),
        name="rms_norm",
    )(xf, weight)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, eps=1e-6):
    """x: [..., H]; weight: [H]."""
    return _fwd_impl(x, weight, eps)


def _rms_fwd(x, weight, eps):
    return _fwd_impl(x, weight, eps), (x, weight)


def _rms_bwd(eps, res, g):
    x, w = res
    H = x.shape[-1]
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    gw = g32 * w32
    dx = r * gw - x32 * (r ** 3) * jnp.sum(gw * x32, axis=-1,
                                           keepdims=True) / H
    dw = jnp.sum((g32 * x32 * r).reshape(-1, H), axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)
