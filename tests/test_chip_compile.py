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
