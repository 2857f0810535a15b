"""Row-stripe stencil helpers shared by the 3x3 Pallas kernels.

A kernel instance sees one full-width ``(stripe, W)`` block plus a
one-tile halo block directly above and below it (8 rows for 32-bit
data).  Neighbour planes come from ``pltpu.roll`` with the wrapped row
or column replaced by the halo row or the edge value, so no unaligned
slice, pad or ``dynamic_slice`` reaches the TPU lowering and only a
tile's worth of halo is read per stripe edge.  Per-stripe scalars
leave the kernel as one ``(8, 128)`` tile each, the smallest block the
TPU's (8, 128) tiling rule admits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["halo_rows", "stripe_specs", "vshift", "hshift", "lane_tile"]

#: block of one per-stripe scalar tile
TILE = (8, 128)


def halo_rows(dtype) -> int:
    """Rows of one native tile of ``dtype``: 8 for 32-bit, 32 for 8-bit."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def stripe_specs(h: int, w: int, bh: int, hb: int):
    """BlockSpecs ``(up halo, stripe, down halo)`` over a grid of stripes.

    Halo blocks are ``hb`` rows; at the image border the index clamps
    and the kernel ignores the block."""
    if bh % hb:
        raise ValueError(f"stripe {bh} is not a multiple of the {hb}-row halo")
    k, last = bh // hb, h // hb - 1
    up = pl.BlockSpec((hb, w), lambda i: (jnp.maximum(i * k - 1, 0), 0))
    mid = pl.BlockSpec((bh, w), lambda i: (i, 0))
    dn = pl.BlockSpec((hb, w), lambda i: (jnp.minimum((i + 1) * k, last), 0))
    return up, mid, dn


def vshift(x, above, below):
    """Planes holding ``x[r-1]`` and ``x[r+1]`` at row ``r``; the rows
    beyond the block are ``above`` and ``below``."""
    rows = x.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    up = jnp.where(r == 0, above, pltpu.roll(x, 1, 0))
    dn = jnp.where(r == rows - 1, below, pltpu.roll(x, rows - 1, 0))
    return up, dn


def hshift(x, left, right):
    """Planes holding ``x[:, c-1]`` and ``x[:, c+1]`` at column ``c``;
    the columns beyond the image are ``left`` and ``right``."""
    w = x.shape[1]
    c = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    lf = jnp.where(c == 0, left, pltpu.roll(x, 1, 1))
    rt = jnp.where(c == w - 1, right, pltpu.roll(x, w - 1, 1))
    return lf, rt


def lane_tile(values) -> jnp.ndarray:
    """An ``(8, 128)`` f32 tile whose lane ``k`` holds ``values[k]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, TILE, 1)
    out = jnp.zeros(TILE, jnp.float32)
    for k, v in enumerate(values):
        out = jnp.where(lane == k, v.astype(jnp.float32), out)
    return out
