"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, on_tpu
from repro.kernels import ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("hw", [(128, 128), (256, 384), (128, 640)])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_color_deconv(hw, dtype):
    h, w = hw
    mk = lambda: jnp.asarray(
        RNG.integers(0, 256, (h, w)).astype(dtype)
        if dtype == np.uint8
        else RNG.uniform(0, 255, (h, w)).astype(dtype)
    )
    r, g, b = mk(), mk(), mk()
    got = ops.color_deconv(r, g, b, block=(128, 128), interpret=True)
    want = ref.color_deconv_ref(r, g, b)
    for gp, wp in zip(got, want):
        np.testing.assert_allclose(gp, wp, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("hw,stripe", [((128, 128), 32), ((256, 256), 64),
                                       ((192, 384), 48)])
@pytest.mark.parametrize("inner", [4, 16])
def test_morph_recon(hw, stripe, inner):
    h, w = hw
    mask = jnp.asarray(RNG.uniform(0, 255, (h, w)).astype(np.float32))
    marker = jnp.maximum(mask - 55.0, 0.0) * jnp.asarray(
        (RNG.uniform(0, 1, (h, w)) > 0.6).astype(np.float32)
    )
    got = ops.morph_recon(marker, mask, stripe=stripe, inner_iters=inner,
                          interpret=True)
    want = ref.morph_recon_ref(marker, mask)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("hw,stripe", [((128, 256), 32), ((256, 128), 64)])
def test_sobel_stats(hw, stripe):
    gray = jnp.asarray(RNG.uniform(0, 255, hw).astype(np.float32))
    mag, st = ops.sobel_stats(gray, stripe=stripe, interpret=True)
    wm, ws = ref.sobel_stats_ref(gray)
    np.testing.assert_allclose(mag, wm, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(st, ws, rtol=1e-4)


@pytest.mark.parametrize("hw,stripe", [((128, 128), 32), ((256, 384), 64),
                                       ((128, 640), 128)])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_feature_fused(hw, stripe, dtype):
    """Fused megakernel == composed deconv + moments + Sobel oracles."""
    h, w = hw
    mk = lambda: jnp.asarray(
        RNG.integers(0, 256, (h, w)).astype(dtype)
        if dtype == np.uint8
        else RNG.uniform(0, 255, (h, w)).astype(dtype)
    )
    r, g, b = mk(), mk(), mk()
    got = ops.feature_fused(r, g, b, stripe=stripe, interpret=True)
    want = ref.feature_fused_ref(r, g, b)
    names = ("hema", "eosin", "mag", "stats")
    for name, gp, wp in zip(names, got, want):
        rtol = 1e-4 if name == "stats" else 3e-5
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(wp), rtol=rtol, atol=1e-4,
            err_msg=name,
        )


BIN_CASES = ("uniform", "one_label", "sparse", "glcm")


def _bins(case, shape, rng):
    """Bin indices of one label-map case, the bin count and ``lo_bits``."""
    n = 8193
    if case == "uniform":
        return rng.integers(0, n, shape), n, 7
    if case == "one_label":  # every pixel collides in one bin
        return np.full(shape, 4321), n, 7
    if case == "sparse":  # mostly background, empty bins, the last label
        idx = np.where(rng.uniform(size=shape) < 0.9, 0,
                       rng.choice([3, 130, 4097, 8192], shape))
        idx[-1, -1] = 8192
        return idx, n, 7
    assert case == "glcm"  # 8 x 8 co-occurrences: index 8 a + b
    return 8 * rng.integers(0, 8, shape) + rng.integers(0, 8, shape), 64, 3


@pytest.mark.parametrize("hw", [(128, 128), (256, 384)])
@pytest.mark.parametrize("case", BIN_CASES)
@pytest.mark.parametrize("channels", [1, 2, 5])
def test_segment_sums(hw, case, channels):
    """One-hot MXU sums == float64 ``np.bincount`` to 1e-5 in every bin.
    Channels as the feature ops use them: one float plane; a boolean
    plane and the count; four float planes and the count."""
    rng = np.random.default_rng((*hw, channels, BIN_CASES.index(case)))
    idx, n_bins, lo_bits = _bins(case, hw, rng)
    n_float = {1: 1, 2: 0, 5: 4}[channels]
    values = [rng.uniform(0, 1e3, hw).astype(np.float32)
              for _ in range(n_float)]
    if channels == 2:
        values.append(rng.uniform(size=hw) < 0.5)
    count = channels > 1
    got = ops.segment_sums(jnp.asarray(idx, jnp.int32),
                           [jnp.asarray(v) for v in values], n_bins=n_bins,
                           count=count, lo_bits=lo_bits, interpret=True)
    flat = idx.reshape(-1)
    want = [np.bincount(flat, v.reshape(-1).astype(np.float64), n_bins)
            for v in values]
    if count:
        want.append(np.bincount(flat, minlength=n_bins).astype(np.float64))
    assert got.shape == (channels, n_bins)
    np.testing.assert_allclose(np.asarray(got, np.float64), np.stack(want),
                               rtol=1e-5, atol=0)


def test_on_tpu_is_cached():
    """Satellite: the backend lookup runs once per process (it is on
    the per-op dispatch path and the backend cannot change)."""
    assert ops.on_tpu() is ops.on_tpu()
    assert ops.on_tpu.cache_info().hits >= 1


@pytest.mark.parametrize("shape", [(1, 2, 128, 64), (2, 4, 256, 64),
                                   (1, 1, 512, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(shape, causal, dtype):
    b, h, s, d = shape
    mk = lambda: jnp.asarray(RNG.normal(0, 1, shape), dtype)
    q, k, v = mk(), mk(), mk()
    got = ops.flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4), (16, 8)])
@pytest.mark.parametrize("s,bk", [(256, 128), (512, 256)])
def test_decode_attention(hq, hkv, s, bk):
    b, d = 3, 64
    q = jnp.asarray(RNG.normal(0, 1, (b, hq, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, s, d)).astype(np.float32))
    lengths = jnp.asarray([s, s // 3, 1], jnp.int32)
    got = ops.decode_attention(q, k, v, lengths, block_k=bk, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("c,h,f", [(4, 2, 128), (16, 8, 256), (32, 4, 512)])
def test_mamba2_chunk_scan(c, h, f):
    decay = jnp.asarray(RNG.uniform(0.3, 1.0, (c, h)).astype(np.float32))
    inc = jnp.asarray(RNG.normal(0, 1, (c, h, f)).astype(np.float32))
    gs, gf = ops.mamba2_chunk_scan(decay, inc, interpret=True)
    ws, wf = ref.mamba2_chunk_scan_ref(decay, inc)
    np.testing.assert_allclose(gs, ws, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gf, wf, rtol=1e-5, atol=1e-5)


def test_backend_dispatch_is_cpu_interpret():
    assert not on_tpu()  # this container runs the interpret path
