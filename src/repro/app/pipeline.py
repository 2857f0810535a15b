"""The hierarchical WSI analysis workflow + variant registration.

Builds the two-level abstract workflow of paper Fig 1/2 over the real
operation implementations and registers the CPU/accelerator function
variants with their calibrated PATS speedup estimates.

Fused-variant substitution rule
-------------------------------
``color_deconv -> {pixel_stats, gradient_stats}`` all read the same
tile, so when the whole feature fan-out lands on one accelerator the
three separate HBM passes are waste.  ``build_workflow(fused=True)``
substitutes the single ``feature_fused`` op for that group (remaining
feature ops hang off it unchanged), and ``register_variants`` binds it
to a composed CPU/accelerator implementation — plus, with
``with_pallas=True``, to the one-pass Pallas megakernel
(:mod:`repro.kernels.feature_fused`) as its ``tpu`` variant.  The
substitution is only profitable when one lane executes the whole
group: a fused op cannot be split across CPU and accelerator lanes, so
deployments whose feature fan-out is routinely spread over lanes (few
accelerators, many host cores) should keep ``fused=False`` and let
device-resident chaining (``WorkerRuntime(chaining=True)``) eliminate
the copies instead.  Its PATS profile is derived from the fused ops'
(``calibration.fused_feature_profile``).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import numpy as np

from ..core.calibration import (
    FUSED_FEATURE_OPS,
    OP_PROFILES,
    PARALLEL_FEATURE_OPS,
    fused_feature_profile,
)
from ..core.variants import VariantRegistry, registry as global_registry
from ..core.workflow import AbstractWorkflow, Operation, Stage
from ..core.worker import OpContext
from . import features as F
from . import segmentation as S

__all__ = ["build_workflow", "register_variants", "run_tile", "OP_IMPLS"]

#: op name -> (cpu impl, accel impl) over the pipeline state dict.
OP_IMPLS: dict[str, tuple[Any, Any]] = {
    "rbc_detection": (S.rbc_detection_cpu, S.rbc_detection_accel),
    "morph_open": (S.morph_open_cpu, S.morph_open_accel),
    "recon_to_nuclei": (S.recon_to_nuclei_cpu, S.recon_to_nuclei_accel),
    "area_threshold": (S.area_threshold_cpu, S.area_threshold_accel),
    "fill_holes": (S.fill_holes_cpu, S.fill_holes_accel),
    "pre_watershed": (S.pre_watershed_cpu, S.pre_watershed_accel),
    "watershed": (S.watershed_cpu, S.watershed_accel),
    "bwlabel": (S.bwlabel_cpu, S.bwlabel_accel),
    "color_deconv": (F.color_deconv_cpu, F.color_deconv_accel),
    "pixel_stats": (F.pixel_stats_cpu, F.pixel_stats_accel),
    "gradient_stats": (F.gradient_stats_cpu, F.gradient_stats_accel),
    "haralick": (F.haralick_cpu, F.haralick_accel),
    "canny_edge": (F.canny_edge_cpu, F.canny_edge_accel),
    "morphometry": (F.morphometry_cpu, F.morphometry_accel),
}

_SEG_ORDER = (
    "rbc_detection",
    "morph_open",
    "recon_to_nuclei",
    "area_threshold",
    "fill_holes",
    "pre_watershed",
    "watershed",
    "bwlabel",
)


def build_workflow(fused: bool = False) -> AbstractWorkflow:
    """The two-level workflow; ``fused=True`` applies the fused-variant
    substitution rule (see module docstring)."""
    seg_ops = [Operation(n) for n in _SEG_ORDER]
    if fused:
        rest = tuple(
            n for n in PARALLEL_FEATURE_OPS if n not in FUSED_FEATURE_OPS
        )
        feat_ops = [Operation("feature_fused")] + [Operation(n) for n in rest]
        feat_edges = tuple(("feature_fused", n) for n in rest)
    else:
        feat_ops = [Operation("color_deconv")] + [
            Operation(n) for n in PARALLEL_FEATURE_OPS
        ]
        feat_edges = tuple(("color_deconv", n) for n in PARALLEL_FEATURE_OPS)
    return AbstractWorkflow.chain(
        "wsi-analysis",
        [
            Stage.chain("segmentation", seg_ops),
            Stage("features", tuple(feat_ops), feat_edges),
        ],
    )


def _to_host(state: Any) -> Any:
    """Download accelerator-produced state for a host-core consumer.

    A CPU lane may receive a state dict whose arrays were produced by
    an accelerator variant (jax arrays); NumPy implementations that
    write in place (``out=``) reject those.  Converting is the
    device->host transfer the runtime's cost model already charges for
    mixed-lane hand-offs — and a no-copy pass-through for host arrays.
    """
    if not isinstance(state, dict):
        return state
    return {
        k: np.asarray(v) if hasattr(v, "__array__") else v
        for k, v in state.items()
    }


def _wrap(fn, to_host: bool = False):
    """Adapt a state-dict function to the OpContext calling convention.

    The first op receives the raw tile (chunk payload); downstream ops
    receive the upstream op's state dict.  Feature ops merge the
    color_deconv state when both are present.  ``to_host=True`` (CPU
    implementations) downloads accelerator-produced input arrays.
    """

    @functools.wraps(fn)
    def impl(ctx: OpContext):
        if not ctx.inputs:
            return fn(ctx.chunk.payload)
        if len(ctx.inputs) == 1:
            state = next(iter(ctx.inputs.values()))
            return fn(_to_host(state) if to_host else state)
        merged: dict[str, Any] = {}
        for v in ctx.inputs.values():
            merged.update(v)
        return fn(_to_host(merged) if to_host else merged)

    return impl


def _feature_fused_cpu(state: dict) -> dict:
    return F.gradient_stats_cpu(F.pixel_stats_cpu(F.color_deconv_cpu(state)))


def _feature_fused_accel(state: dict) -> dict:
    return F.gradient_stats_accel(
        F.pixel_stats_accel(F.color_deconv_accel(state))
    )


def register_variants(
    reg: VariantRegistry | None = None, accel_kind: str = "gpu",
    with_pallas: bool = False,
) -> VariantRegistry:
    reg = reg or global_registry
    for name, (cpu_fn, accel_fn) in OP_IMPLS.items():
        p = OP_PROFILES[name]
        reg.register(name, "cpu", _wrap(cpu_fn, to_host=True), speedup=1.0)
        reg.register(
            name,
            accel_kind,
            _wrap(accel_fn),
            speedup=p.gpu_speedup,
            transfer_impact=p.transfer_impact,
        )
    # Fused feature megakernel variant (substitution rule: docstring).
    fp = fused_feature_profile()
    reg.register("feature_fused", "cpu",
                 _wrap(_feature_fused_cpu, to_host=True), speedup=1.0)
    reg.register(
        "feature_fused",
        accel_kind,
        _wrap(_feature_fused_accel),
        speedup=fp.gpu_speedup,
        transfer_impact=fp.transfer_impact,
    )
    if with_pallas:
        _register_pallas_variants(reg)
    return reg


#: Dilations per HBM pass in ``fill_holes``' reconstruction.  On a TPU
#: v5e at 4096² the fixpoint takes 33 sweeps of 64 in 0.178 s, against
#: 129 of the kernel's default 16 in 0.191 s (and 65 of 32 in 0.181 s).
_FILL_HOLES_INNER_ITERS = 64


@jax.jit
def _fill_holes_accel_pallas(mask):
    """``fill_holes`` as one program whose geodesic reconstruction (a
    fixpoint as deep as half the tile's side) runs in the Pallas
    block-synchronous kernel.  The jit name keeps ``_fill_holes_accel``,
    by which ``bench/metrics/_stages.py`` finds the op's device time, and
    leaves out ``morph_recon``, whose roofline counts only
    ``recon_to_nuclei``'s calls of the kernel."""
    from ..kernels import ops as K

    return S._fill_holes_j(mask, functools.partial(
        K.morph_recon, inner_iters=_FILL_HOLES_INNER_ITERS))


def _register_pallas_variants(reg: VariantRegistry) -> None:
    """Bind the Pallas kernels as ``tpu`` variants of their ops
    (compiled on TPU, interpret mode on the CPU backend).  The
    reconstruction kernel (:mod:`repro.kernels.morph_recon`) has two
    users: ``recon_to_nuclei`` and ``fill_holes``; the one-hot
    reduction (:mod:`repro.kernels.segment_sums`) has four, the feature
    ops' per-object sums and GLCM.  The ``gpu``/``accel`` variants keep
    XLA's scatter-adds: on the CPU backend a scatter is one add a pixel
    against the contraction's thousands."""
    import jax.numpy as jnp

    from ..kernels import ops as K

    def color_deconv_pallas(ctx: OpContext):
        state = dict(next(iter(ctx.inputs.values())))
        rgb = np.asarray(state["rgb"], np.float32)
        hema, eosin, _ = K.color_deconv(
            jnp.asarray(rgb[..., 0]), jnp.asarray(rgb[..., 1]),
            jnp.asarray(rgb[..., 2]), block=(128, 128),
        )
        return {**state, "hema": hema, "eosin": eosin}

    def recon_pallas(ctx: OpContext):
        state = dict(next(iter(ctx.inputs.values())))
        gray = jnp.asarray(state["gray"], jnp.float32)
        inv = 255.0 - gray
        # Marker via iterated erosion (XLA), then the Pallas
        # block-synchronous reconstruction for the fixpoint hot loop.
        from .segmentation import _erode_j

        marker = inv
        for _ in range(8):
            marker = _erode_j(marker)
        recon = K.morph_recon(marker, inv)
        nuclei = ((inv - recon) > 25.0) & jnp.asarray(state["fg_open"])
        return {**state, "recon": recon, "nuclei": nuclei}

    def fill_holes_pallas(state: dict) -> dict:
        return {**state, "mask": _fill_holes_accel_pallas(state["mask_at"])}

    def feature_fused_pallas(ctx: OpContext):
        # One VMEM pass: deconv planes + Sobel |grad| of the luminance
        # in a single HBM read, then one MXU reduction over objects.
        state = dict(next(iter(ctx.inputs.values())))
        rgb = np.asarray(state["rgb"], np.float32)
        hema, eosin, mag, _ = K.feature_fused(
            jnp.asarray(rgb[..., 0]), jnp.asarray(rgb[..., 1]),
            jnp.asarray(rgb[..., 2]),
        )
        feat_pixel, feat_gradient = F._pixel_gradient_stats_j(
            hema, mag, jnp.asarray(state["objects"]))
        return {
            **state,
            "hema": hema,
            "eosin": eosin,
            "feat_pixel": feat_pixel,
            "feat_gradient": feat_gradient,
        }

    p = OP_PROFILES["color_deconv"]
    reg.register("color_deconv", "tpu", color_deconv_pallas,
                 speedup=p.gpu_speedup, transfer_impact=p.transfer_impact)
    p = OP_PROFILES["recon_to_nuclei"]
    reg.register("recon_to_nuclei", "tpu", recon_pallas,
                 speedup=p.gpu_speedup, transfer_impact=p.transfer_impact)
    p = OP_PROFILES["fill_holes"]
    reg.register("fill_holes", "tpu", _wrap(fill_holes_pallas),
                 speedup=p.gpu_speedup, transfer_impact=p.transfer_impact)
    fp = fused_feature_profile()
    reg.register("feature_fused", "tpu", feature_fused_pallas,
                 speedup=fp.gpu_speedup, transfer_impact=fp.transfer_impact)
    # Per-object sums and the GLCM as one-hot MXU contractions
    # (:mod:`repro.kernels.segment_sums`) instead of scatter-adds.
    for name, fn in (("haralick", F.haralick_accel),
                     ("canny_edge", F.canny_edge_accel),
                     ("morphometry", F.morphometry_accel)):
        p = OP_PROFILES[name]
        reg.register(name, "tpu", _wrap(functools.partial(fn, mxu=True)),
                     speedup=p.gpu_speedup,
                     transfer_impact=p.transfer_impact)


def run_tile(tile: np.ndarray, variant: str = "cpu") -> dict:
    """Reference single-threaded execution of the full pipeline."""
    idx = 0 if variant == "cpu" else 1
    state: Any = tile
    for name in _SEG_ORDER + ("color_deconv",):
        state = OP_IMPLS[name][idx](state)
    for name in PARALLEL_FEATURE_OPS:
        state = OP_IMPLS[name][idx](state)
    return state
