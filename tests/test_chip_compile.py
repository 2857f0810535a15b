"""The WSI Pallas kernels compile for a TPU v5e at 4096² tiles.

Compiled (``interpret=False``) for a described, not attached, v5e chip
at the shapes and block sizes the pipeline's ``tpu`` variants use, so a
block shape, lowering or VMEM budget the chip's compiler refuses fails
here instead of on the chip.  Nothing runs.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.color_deconv import color_deconv_pallas
from repro.kernels.feature_fused import feature_fused_pallas
from repro.kernels.morph_recon import morph_recon_pallas
from repro.kernels.segment_sums import segment_sums_pallas
from repro.kernels.sobel_stats import sobel_stats_pallas

SIDE = 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


# name -> (kernel with the pipeline's block sizes, number of f32 planes);
# the stripe kernels run at their default stripes.
KERNELS = {
    "color_deconv": (
        functools.partial(color_deconv_pallas, block=(128, 128)), 3
    ),
    "morph_recon": (morph_recon_pallas, 2),
    "feature_fused": (feature_fused_pallas, 3),
    "sobel_stats": (sobel_stats_pallas, 1),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, n_planes = KERNELS[name]
    plane = jax.ShapeDtypeStruct((SIDE, SIDE), jnp.float32, sharding=one_chip)
    compiled = jax.jit(fn).lower(*([plane] * n_planes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fill_holes_program_compiles_for_v5e(one_chip, monkeypatch):
    """The ``tpu`` variant's whole ``fill_holes`` program, with the Pallas
    reconstruction compiled, not interpreted, inside it."""
    from repro.app.pipeline import _fill_holes_accel_pallas
    from repro.kernels import ops

    # The CPU backend is the default here: steer the kernel wrapper to
    # the compiled kernel the chip would run.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    plane = jax.ShapeDtypeStruct((SIDE, SIDE), jnp.bool_, sharding=one_chip)
    compiled = _fill_holes_accel_pallas.lower(plane).compile()
    assert "tpu_custom_call" in compiled.as_text()


# feature op -> (dtypes of the value planes, count, bins, lo_bits): the
# tables the ``tpu`` variants reduce into, 5 x 65x128, 2 x 65x128, 8x8.
SEGMENT_SUMS = {
    "feature_fused": ((jnp.float32,) * 4, True, 8193, 7),
    "canny_edge-morphometry": ((jnp.bool_,), True, 8193, 7),
    "haralick": ((jnp.bool_,), False, 64, 3),
}


@pytest.mark.parametrize("layout", sorted(SEGMENT_SUMS))
def test_segment_sums_compiles_for_v5e(one_chip, layout):
    dtypes, count, n_bins, lo_bits = SEGMENT_SUMS[layout]
    idx = jax.ShapeDtypeStruct((SIDE, SIDE), jnp.int32, sharding=one_chip)
    planes = [jax.ShapeDtypeStruct((SIDE, SIDE), d, sharding=one_chip)
              for d in dtypes]
    fn = functools.partial(segment_sums_pallas, n_bins=n_bins, count=count,
                           lo_bits=lo_bits)
    compiled = jax.jit(lambda i, *v: fn(i, v)).lower(idx, *planes).compile()
    assert "tpu_custom_call" in compiled.as_text()
