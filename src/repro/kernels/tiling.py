"""Tile-shape rules shared by the Pallas kernels and their jnp oracles.

Mosaic (the Pallas TPU lowering) accepts a block whose last two dims are
multiples of (sublane rows, 128 lanes) or equal to the array's dims, and it
lays every VMEM value out in (sublane, 128) tiles.  ``fit_block`` turns a
requested block into one that obeys the first rule; ``padded_bytes`` counts a
2-D VMEM value the way the second rule stores it, which is what the kernels'
VMEM models sum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


def sublanes(dtype) -> int:
    """Rows of one native VMEM tile: 8 for 32-bit, 16 for 16-bit, 32 for
    8-bit values (narrow types pack along sublanes)."""
    return 32 // jnp.dtype(dtype).itemsize


def round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def fit_block(block: int, dim: int, align: int) -> int:
    """A legal block for an axis of length ``dim``: the whole axis when the
    request covers it, else the request rounded up to ``align``."""
    if block >= dim:
        return dim
    block = round_up(max(block, 1), align)
    return dim if block >= dim else block


def fit_rows(block: int, rows: int, dtype) -> int:
    """Row block for ``rows`` rows of ``dtype``: a multiple of the native
    sublane count, no larger than the rows rounded up to it (the caller
    pads the rows to a multiple of the block)."""
    sub = sublanes(dtype)
    return min(round_up(max(block, 1), sub), round_up(max(rows, 1), sub))


def padded_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a (rows, cols) value in native (sublane, 128) tiles."""
    return (round_up(rows, sublanes(dtype)) * round_up(cols, LANES)
            * jnp.dtype(dtype).itemsize)


def mxu_precision(dtype):
    """Contraction precision for operands of ``dtype``: f32 operands
    contract at full f32 precision (``HIGHEST``; otherwise the TPU may
    round them to bf16), narrower ones at the default."""
    if np.dtype(dtype) == np.float32:      # a static dtype, not a value
        return jax.lax.Precision.HIGHEST
    return None


def gemm_rows(x: jax.Array) -> jax.Array:
    """Pad a single-row operand to two rows.

    XLA lowers an M=1 contraction as a matrix-vector product whose
    accumulation order differs from the matrix-matrix tiles the kernels
    run, so the jnp paths contract at least two rows to stay bitwise
    equal to the interpret-mode kernels; callers slice the row back off.
    """
    if x.shape[0] != 1:
        return x
    return jnp.pad(x, ((0, 1), (0, 0)))
