"""Pallas TPU kernel: per-pixel sums into a small table, on the MXU.

``table[c, idx[p]] += v_c[p]`` over a tile is a scatter-add, and on the
TPU a scatter of 16.7M pixels into a few thousand bins serializes on
the colliding indices (the background bin alone takes most pixels).
This kernel computes the same table as a contraction over pixels.
Each bin index splits into a high part ``idx >> lo_bits`` and a low
part ``idx & (2**lo_bits - 1)``, and

    S[c, hi, lo] = sum_p v_c[p] [hi_p = hi] [lo_p = lo]
                 = ((onehot_hi * v_c) . onehot_lo^T)[hi, lo],

one matmul per image row, contracted over the row's pixels.  Per-object
sums over labels ``0..8192`` take ``lo_bits=7`` (65 x 128 bins); an 8 x 8
co-occurrence table takes ``lo_bits=3`` with ``idx = 8 a + b``.

Exactness: a one-hot is exact in bfloat16.  A float32 value is split
into three bfloat16 pieces whose sum is the value to 2**-27 of it (a
boolean plane is one piece), each product of a piece and a one-hot is
exact, and the MXU accumulates in float32: the table is a float32 sum,
as a scatter-add's is, not a bfloat16 one.

Layout: the grid runs over (row block, lane chunk), both reduction
axes, and the ``[pieces x hi, 128]`` float32 table is the output
block, resident across the grid.  Both one-hots are built in VMEM with
the tile's width on lanes and contracted as an NT matmul (the last
axis of both), so no one-hot reaches HBM and none is relaid out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["segment_sums_pallas"]

#: Rows of the low one-hot: one MXU width, so ``lo_bits`` is at most 7.
LO = 128
#: Sublane tile of bfloat16, to which the high one-hot's rows round up.
_BF16_ROWS = 16
#: Image rows a grid step reads, one matmul each.
_ROWS = 8
#: Widest lane chunk a grid step reads (a multiple of 128 dividing the width).
_MAX_LANES = 512


def _pieces(v, n):
    """``n`` float32 planes, each exact in bfloat16 but the last (which
    the caller rounds), that sum to ``v``."""
    out = []
    for _ in range(n - 1):
        p = v.astype(jnp.bfloat16).astype(jnp.float32)
        out.append(p)
        v = v - p
    return out + [v]


def _kernel(idx_ref, *refs, pieces, count, lo_bits, nh):
    val_refs, out_ref = refs[:-1], refs[-1]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows, width = idx_ref.shape
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (nh, width), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LO, width), 0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for r in range(rows):
        idx = idx_ref[r:r + 1, :]
        on_hi = hi_iota == (idx >> lo_bits)
        onehot_lo = jnp.where(
            lo_iota == (idx & ((1 << lo_bits) - 1)), 1.0, 0.0
        ).astype(jnp.bfloat16)
        blocks = [
            jnp.where(on_hi, p, 0.0).astype(jnp.bfloat16)
            for ref, n in zip(val_refs, pieces)
            for p in _pieces(ref[r:r + 1, :], n)
        ]
        if count:
            blocks.append(jnp.where(on_hi, 1.0, 0.0).astype(jnp.bfloat16))
        acc = acc + jax.lax.dot_general(
            jnp.concatenate(blocks, axis=0), onehot_lo,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
    out_ref[...] += acc


def _lane_chunk(width: int, most: int) -> int:
    """The widest multiple of 128 up to ``most`` that divides ``width``."""
    return max(c for c in range(128, min(most, width) + 1, 128)
               if width % c == 0)


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "count", "lo_bits", "interpret"),
)
def segment_sums_pallas(
    idx: jnp.ndarray,
    values,
    *,
    n_bins: int,
    count: bool = False,
    lo_bits: int = 7,
    interpret: bool = False,
):
    """Sums of each plane of ``values`` over the pixels of each bin.

    ``idx`` is an (H, W) integer plane of bin indices; a pixel whose
    index lies outside ``0..n_bins-1`` counts in no bin, as in
    ``jax.ops.segment_sum``.  ``values`` is a sequence of (H, W) planes,
    boolean (summed as 0/1 in one MXU pass) or float (float32, three
    passes).  ``count=True`` appends the pixel count of each bin.
    Returns a float32 ``(len(values) + count, n_bins)`` table.
    """
    if not 1 <= lo_bits <= 7:
        raise ValueError(f"lo_bits {lo_bits} outside 1..7")
    values = tuple(values)
    pieces = tuple(1 if v.dtype == jnp.bool_ else 3 for v in values)
    if not pieces and not count:
        raise ValueError("nothing to sum: no values and no count")
    h, w = idx.shape
    bh = _ROWS
    hp, wp = -(-h // bh) * bh, -(-w // LO) * LO
    bw = _lane_chunk(wp, _MAX_LANES)
    # Padding pixels take index -1: they match no high row.
    idx = jnp.pad(idx.astype(jnp.int32), ((0, hp - h), (0, wp - w)),
                  constant_values=-1)
    planes = [jnp.pad(v.astype(jnp.float32), ((0, hp - h), (0, wp - w)))
              for v in values]
    n_hi = -(-n_bins // (1 << lo_bits))
    nh = -(-n_hi // _BF16_ROWS) * _BF16_ROWS
    n_blocks = sum(pieces) + count
    spec = pl.BlockSpec((bh, bw), lambda i, j: (i, j))
    table = pl.pallas_call(
        functools.partial(_kernel, pieces=pieces, count=count,
                          lo_bits=lo_bits, nh=nh),
        grid=(hp // bh, wp // bw),
        in_specs=[spec] * (1 + len(planes)),
        out_specs=pl.BlockSpec((n_blocks * nh, LO), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * nh, LO), jnp.float32),
        interpret=interpret,
    )(idx, *planes)
    table = table.reshape(n_blocks, nh, LO)
    starts = [0]
    for n in pieces + ((1,) if count else ()):
        starts.append(starts[-1] + n)
    sums = jnp.stack([table[a:b].sum(axis=0)
                      for a, b in zip(starts, starts[1:])])
    sums = sums[:, :n_hi, :1 << lo_bits].reshape(len(starts) - 1, -1)
    return sums[:, :n_bins]
