"""Grouped matrix product: rows sorted by group, one weight matrix a group.

    out[r] = x[r] @ w[group of r],   x [R, K], w [E, K, N], sizes [E]

Row r belongs to group e when it lies in [sum(sizes[:e]), sum(sizes[:e+1])).
Rows past sum(sizes) belong to no group: what `out` holds there is
UNDEFINED on the chip (the kernel never visits those tiles), so a caller
masks them, and masks the rows of `x` too, whose cotangent is as
undefined. On a TPU this is the megablox Pallas kernel that ships with
jax (`gmm`, with `tgmm` for the weight gradient); elsewhere
`jax.lax.ragged_dot`, which XLA lowers itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ._tpu import on_tpu as _on_tpu

__all__ = ["grouped_matmul", "supported", "ROW_TILE"]

ROW_TILE = 512         # the row buffer of a Pallas route divides by this
_TILING = (ROW_TILE, 512, 512)


def supported(rows: int, k: int, n: int) -> bool:
    """The Pallas route's shapes: whole row tiles, lane-aligned K and N."""
    return _on_tpu() and rows % ROW_TILE == 0 and k % 128 == 0 \
        and n % 128 == 0


def grouped_matmul(x, w, sizes):
    """x [R, K] @ w [E, K, N] by `sizes` [E] int32 -> [R, N] in x's dtype."""
    rows, k = x.shape
    n = w.shape[-1]
    sizes = sizes.astype(jnp.int32)
    if supported(rows, k, n):
        from jax.experimental.pallas.ops.tpu.megablox import ops
        return ops.gmm(x, w, sizes, preferred_element_type=x.dtype,
                       tiling=_TILING)
    return jax.lax.ragged_dot(x, w, sizes)
