"""What every Pallas kernel here asks of the device: is this a TPU, and
how many rows of a row-blocked kernel fit the kernel's VMEM.

`on_tpu` is THE backend test of kernels/ — each kernel module imports it
as `_on_tpu` (tests steer one module's route by monkeypatching that
name). A backend that fails to initialise raises out of it: "the chip is
broken" must never read as "not on a TPU" and quietly take a dense jnp
route.
"""
from __future__ import annotations

import jax

# Mosaic tiles the last two dims of every VMEM block as (8, 128) 32-bit
# words; 16-bit dtypes pack two rows per sublane, so 16 rows keeps a
# bf16 block whole-tile as well
SUBLANES = 16
LANES = 128

# what a kernel's blocks (double-buffered operands + scratch + f32
# temporaries) may add up to: v5e scopes a kernel to 16 MiB of its
# 128 MiB VMEM unless CompilerParams(vmem_limit_bytes=) says otherwise,
# and the compiler's own stack needs room under the same limit
VMEM_BLOCK_BUDGET = 10 * 1024 * 1024


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def row_block(rows: int, bytes_per_row: int, want: int = 256) -> int:
    """Rows per grid step of a kernel whose rows are independent: the
    largest multiple of SUBLANES <= `want` whose blocks fit
    VMEM_BLOCK_BUDGET — or all the rows in one block when there are no
    more than that (a block equal to the array's extent always tiles).
    The caller's grid is pl.cdiv(rows, block): Pallas pads the ragged
    last block on read and drops its out-of-range rows on write."""
    fit = VMEM_BLOCK_BUDGET // max(1, bytes_per_row)
    blk = max(SUBLANES, min(want, fit) // SUBLANES * SUBLANES)
    return rows if rows <= blk else blk
