"""Jit'd public wrappers around the Pallas kernels (function variants).

Each wrapper picks the execution mode for the current backend:

* on TPU — the compiled Pallas kernel,
* on CPU — the same kernel body in interpret mode (correctness),
* on any other backend — an error, never a silent interpreter.

These are registered as the ``tpu`` function variants of the
corresponding logical operations, so the middleware's variant mechanism
(paper §III-A) picks them up transparently.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .color_deconv import color_deconv_pallas
from .decode_attention import decode_attention_pallas
from .feature_fused import feature_fused_pallas
from .flash_attention import flash_attention_pallas
from .mamba2_scan import mamba2_chunk_scan_pallas
from .morph_recon import morph_recon_pallas
from .sobel_stats import sobel_stats_pallas

__all__ = [
    "on_tpu",
    "color_deconv",
    "morph_recon",
    "sobel_stats",
    "feature_fused",
    "flash_attention",
    "decode_attention",
    "mamba2_chunk_scan",
]


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    # Called on every op dispatch; the backend cannot change
    # mid-process, so one jax.default_backend() lookup suffices.
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=1)
def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on TPU or interpreted on CPU; "
            f"the {backend!r} backend has neither"
        )
    return backend == "cpu"


def color_deconv(r, g, b, **kw):
    kw.setdefault("interpret", _interpret())
    return color_deconv_pallas(r, g, b, **kw)


def morph_recon(marker, mask, **kw):
    kw.setdefault("interpret", _interpret())
    return morph_recon_pallas(marker, mask, **kw)


def sobel_stats(gray, **kw):
    kw.setdefault("interpret", _interpret())
    return sobel_stats_pallas(gray, **kw)


def feature_fused(r, g, b, **kw):
    kw.setdefault("interpret", _interpret())
    return feature_fused_pallas(r, g, b, **kw)


def flash_attention(q, k, v, *, causal: bool = True, **kw):
    kw.setdefault("interpret", _interpret())
    return flash_attention_pallas(q, k, v, causal=causal, **kw)


def decode_attention(q, k, v, lengths, **kw):
    kw.setdefault("interpret", _interpret())
    return decode_attention_pallas(q, k, v, lengths, **kw)


def mamba2_chunk_scan(decay, inc, **kw):
    kw.setdefault("interpret", _interpret())
    return mamba2_chunk_scan_pallas(decay, inc, **kw)


#: oracle references, re-exported for tests/benchmarks
oracles = {
    "color_deconv": ref.color_deconv_ref,
    "morph_recon": ref.morph_recon_ref,
    "sobel_stats": ref.sobel_stats_ref,
    "feature_fused": ref.feature_fused_ref,
    "flash_attention": ref.flash_attention_ref,
    "decode_attention": ref.decode_attention_ref,
    "mamba2_chunk_scan": ref.mamba2_chunk_scan_ref,
}
