"""Pallas TPU kernel: morphological reconstruction (block-synchronous).

The paper's GPU implementation uses hierarchical queues and wave
propagation — data-dependent control flow that is hostile to the TPU's
VPU.  TPU-native rethink: *block-synchronous iterated geodesic
dilation*.  The image is cut into full-width row stripes; each stripe
runs ``inner_iters`` local 8-connected max-propagation sweeps clamped
by the mask entirely in VMEM, exchanging one halo row with its
neighbours per outer sweep.  An SMEM-style change flag per stripe lets
the host ``lax.while_loop`` stop at the global fixpoint, which equals
Vincent's sequential reconstruction (the fixpoint is unique and
propagation order only affects the iteration count).

Stripes keep the lane dimension = image width (multiple of 128), so
every vector op is fully populated; the halo is one 8-row tile per
stripe edge (:mod:`repro.kernels.stencil`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .stencil import TILE, halo_rows, hshift, lane_tile, stripe_specs, vshift

__all__ = ["morph_recon_pallas", "morph_recon_step"]

_NEG = -3.0e38  # effectively -inf for f32 image data


def _kernel(up_ref, c_ref, dn_ref, mask_ref, out_ref, changed_ref, *,
            inner_iters, hb):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    c = c_ref[...]
    mask = mask_ref[...]
    # Halo rows stay fixed until the next outer exchange; beyond the
    # image they are -inf.
    above = jnp.where(i == 0, _NEG, up_ref[hb - 1:hb, :])
    below = jnp.where(i == n - 1, _NEG, dn_ref[0:1, :])

    def sweep(_, x):
        # 8-connected max, separably: rows first, then columns.
        up, dn = vshift(x, above, below)
        v = jnp.maximum(jnp.maximum(up, x), dn)
        lf, rt = hshift(v, _NEG, _NEG)
        return jnp.minimum(jnp.maximum(jnp.maximum(lf, v), rt), mask)

    new_c = jax.lax.fori_loop(0, inner_iters, sweep, c)
    out_ref[...] = new_c
    changed_ref[...] = lane_tile([jnp.max(jnp.where(new_c != c, 1.0, 0.0))])


@functools.partial(jax.jit, static_argnames=("stripe", "inner_iters", "interpret"))
def morph_recon_step(
    marker: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    stripe: int = 64,
    inner_iters: int = 16,
    interpret: bool = False,
):
    """One outer block-synchronous sweep. Returns (new_marker, changed)."""
    h, w = marker.shape
    bh = min(stripe, h)
    if h % bh:
        raise ValueError(f"height {h} not divisible by stripe {bh}")
    n = h // bh
    hb = halo_rows(marker.dtype)
    up, mid, dn = stripe_specs(h, w, bh, hb)
    new_marker, changed = pl.pallas_call(
        functools.partial(_kernel, inner_iters=inner_iters, hb=hb),
        grid=(n,),
        in_specs=[up, mid, dn, mid],
        out_specs=(mid, pl.BlockSpec(TILE, lambda i: (i, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((h, w), marker.dtype),
            jax.ShapeDtypeStruct((n * TILE[0], TILE[1]), jnp.float32),
        ),
        interpret=interpret,
    )(marker, marker, marker, mask)
    return new_marker, jnp.any(changed > 0)


@functools.partial(jax.jit, static_argnames=("stripe", "inner_iters", "interpret"))
def morph_recon_pallas(
    marker: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    stripe: int = 64,
    inner_iters: int = 16,
    interpret: bool = False,
):
    """Run block-synchronous sweeps to the global fixpoint."""
    marker = jnp.minimum(marker.astype(jnp.float32), mask.astype(jnp.float32))
    mask = mask.astype(jnp.float32)

    def cond(s):
        _, changed = s
        return changed

    def body(s):
        m, _ = s
        return morph_recon_step(
            m, mask, stripe=stripe, inner_iters=inner_iters, interpret=interpret
        )

    out, _ = jax.lax.while_loop(cond, body, (marker, jnp.array(True)))
    return out
