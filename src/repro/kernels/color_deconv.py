"""Pallas TPU kernel: color deconvolution (stain separation).

Per-pixel optical-density transform followed by a 3x3 stain-matrix
solve — pure VPU elementwise work on (block_h, block_w) VMEM tiles.
Channel planes are separate (H, W) arrays so every load/store is a
contiguous lane-aligned tile (layout chosen for the TPU memory
hierarchy rather than the interleaved RGB of the CUDA original).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import DECONV_MATRIX

__all__ = ["color_deconv_pallas"]


def _kernel(r_ref, g_ref, b_ref, hema_ref, eosin_ref, resid_ref, *, m):
    od = lambda x: -jnp.log10((x.astype(jnp.float32) + 1.0) / 256.0)
    odr, odg, odb = od(r_ref[...]), od(g_ref[...]), od(b_ref[...])
    hema_ref[...] = m[0][0] * odr + m[0][1] * odg + m[0][2] * odb
    eosin_ref[...] = m[1][0] * odr + m[1][1] * odg + m[1][2] * odb
    resid_ref[...] = m[2][0] * odr + m[2][1] * odg + m[2][2] * odb


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def color_deconv_pallas(
    r: jnp.ndarray,
    g: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block: tuple[int, int] = (256, 256),
    interpret: bool = False,
):
    h, w = r.shape
    bh, bw = min(block[0], h), min(block[1], w)
    if h % bh or w % bw:
        raise ValueError(f"image {h}x{w} not divisible by block {bh}x{bw}")
    grid = (h // bh, w // bw)
    spec = pl.BlockSpec((bh, bw), lambda i, j: (i, j))
    m = tuple(tuple(float(x) for x in row) for row in DECONV_MATRIX)
    out = jax.ShapeDtypeStruct((h, w), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, m=m),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec, spec),
        out_shape=(out, out, out),
        interpret=interpret,
    )(r, g, b)
