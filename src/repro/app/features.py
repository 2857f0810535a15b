"""Feature-computation-stage operations (paper §II, Table I).

``color_deconv`` separates hematoxylin/eosin stains; the five feature
ops are mutually independent given the deconvolved channels and the
object label map — the concurrency PATS exploits.  Every op has a CPU
(NumPy) and an accelerator (jit'd jnp) variant with identical outputs.

Per-object features use fixed-shape segment reductions over
``objects`` in ``1..MAX_OBJECTS`` so the accelerated variants compile
once per tile size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .segmentation import MAX_OBJECTS, to_gray

__all__ = [
    "STAIN_MATRIX",
    "color_deconv_cpu", "color_deconv_accel",
    "pixel_stats_cpu", "pixel_stats_accel",
    "gradient_stats_cpu", "gradient_stats_accel",
    "haralick_cpu", "haralick_accel",
    "canny_edge_cpu", "canny_edge_accel",
    "morphometry_cpu", "morphometry_accel",
]

# Ruifrok & Johnston H&E(+residual) stain vectors, rows normalized.
STAIN_MATRIX = np.array(
    [
        [0.650, 0.704, 0.286],   # hematoxylin
        [0.072, 0.990, 0.105],   # eosin
        [0.268, 0.570, 0.776],   # residual
    ],
    dtype=np.float32,
)
_DECONV = np.linalg.inv(STAIN_MATRIX.T).astype(np.float32)

_SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], np.float32)
_SOBEL_Y = _SOBEL_X.T.copy()


# --------------------------------------------------------------------------
# color deconvolution
# --------------------------------------------------------------------------


def _od(rgb_f):
    return -np.log10((rgb_f + 1.0) / 256.0)


def color_deconv_cpu(state: dict) -> dict:
    rgb = np.asarray(state["rgb"], np.float32)
    od = _od(rgb)
    stains = od.reshape(-1, 3) @ _DECONV.T
    stains = stains.reshape(od.shape).astype(np.float32)
    return {**state, "hema": stains[..., 0], "eosin": stains[..., 1]}


@jax.jit
def _deconv_j(rgb: jnp.ndarray):
    od = -jnp.log10((rgb.astype(jnp.float32) + 1.0) / 256.0)
    stains = od.reshape(-1, 3) @ jnp.asarray(_DECONV).T
    stains = stains.reshape(od.shape)
    return stains[..., 0], stains[..., 1]


def color_deconv_accel(state: dict) -> dict:
    hema, eosin = _deconv_j(jnp.asarray(np.asarray(state["rgb"])))
    return {**state, "hema": hema, "eosin": eosin}


# --------------------------------------------------------------------------
# per-object reductions
# --------------------------------------------------------------------------


def _seg_sums_np(values: np.ndarray, objects: np.ndarray):
    flat_v, flat_o = values.reshape(-1), objects.reshape(-1).astype(np.int64)
    n = MAX_OBJECTS + 1
    s = np.bincount(flat_o, weights=flat_v, minlength=n)[:n]
    s2 = np.bincount(flat_o, weights=flat_v * flat_v, minlength=n)[:n]
    cnt = np.bincount(flat_o, minlength=n)[:n]
    return s[1:], s2[1:], cnt[1:]  # drop background


def _obj_stats_np(values: np.ndarray, objects: np.ndarray) -> np.ndarray:
    s, s2, cnt = _seg_sums_np(values, objects)
    safe = np.maximum(cnt, 1)
    mean = s / safe
    var = np.maximum(s2 / safe - mean * mean, 0.0)
    return np.stack([mean, np.sqrt(var), cnt.astype(np.float64)], axis=-1).astype(
        np.float32
    )


def _moments_j(s, s2, cnt, shift=0.0) -> jnp.ndarray:
    """Per-object ``[mean, std, count]`` from the sums over objects
    ``0..MAX_OBJECTS`` (background dropped) of ``v - shift`` and its
    square."""
    s, s2, cnt = s[1:], s2[1:], cnt[1:]
    safe = jnp.maximum(cnt, 1.0)
    mean = s / safe
    var = jnp.maximum(s2 / safe - mean * mean, 0.0)
    mean = jnp.where(cnt > 0, mean + shift, 0.0)
    return jnp.stack([mean, jnp.sqrt(var), cnt], axis=-1).astype(jnp.float32)


def _table_sums(idx: jnp.ndarray, values, *, n_bins: int, count: bool = False,
                mxu: bool, lo_bits: int = 7) -> jnp.ndarray:
    """Float32 ``(len(values) + count, n_bins)`` table: the sum of each
    plane of ``values`` (and the pixel count) over the pixels of each
    bin of ``idx``.  ``mxu`` (the ``tpu`` variants) contracts one-hots
    on the MXU (:mod:`repro.kernels.segment_sums`): on the TPU a
    scatter-add into a small table serializes on colliding bins.  The
    ``accel`` variants keep one ``segment_sum`` scatter per plane, one
    add a pixel where the contraction costs thousands on the CPU."""
    if mxu:
        from ..kernels import ops as K

        return K.segment_sums(idx, values, n_bins=n_bins, count=count,
                              lo_bits=lo_bits)
    flat = idx.reshape(-1)
    planes = [v.reshape(-1).astype(jnp.float32) for v in values]
    if count:
        planes.append(jnp.ones(flat.shape, jnp.float32))
    return jnp.stack([jax.ops.segment_sum(p, flat, num_segments=n_bins)
                      for p in planes])


def _object_sums(objects: jnp.ndarray, values, *, count: bool = False,
                 mxu: bool) -> jnp.ndarray:
    """:func:`_table_sums` over objects ``0..MAX_OBJECTS``."""
    return _table_sums(objects, values, n_bins=MAX_OBJECTS + 1, count=count,
                       mxu=mxu)


def _obj_stats_j(values: jnp.ndarray, objects: jnp.ndarray) -> jnp.ndarray:
    return _moments_j(*_object_sums(objects, (values, values * values),
                                    count=True, mxu=False))


@jax.jit
def _pixel_gradient_stats_j(hema: jnp.ndarray, mag: jnp.ndarray,
                            objects: jnp.ndarray):
    """``feat_pixel`` and ``feat_gradient`` of the fused ``tpu`` variant:
    one MXU reduction of ``[h, h², m, m², 1]`` over objects, ``h`` and
    ``m`` being ``hema`` and ``mag`` less their mean over the objects'
    pixels.  Sums of squares about a value near each object's mean keep
    the float32 digits that ``var = E[v²] - mean²`` would cancel.  The
    jit name holds ``_gradient_stats_j``, by which
    ``bench/metrics/_stages.py`` counts it in the feature stage, and
    leaves out ``feature_fused``, whose roofline counts the megakernel
    alone."""
    fg = objects > 0
    n_fg = jnp.maximum(fg.sum(), 1).astype(jnp.float32)
    c_h = jnp.where(fg, hema, 0.0).sum() / n_fg
    c_m = jnp.where(fg, mag, 0.0).sum() / n_fg
    h, m = hema - c_h, mag - c_m
    s_h, s2_h, s_m, s2_m, cnt = _object_sums(
        objects, (h, h * h, m, m * m), count=True, mxu=True)
    return _moments_j(s_h, s2_h, cnt, c_h), _moments_j(s_m, s2_m, cnt, c_m)


# --------------------------------------------------------------------------
# pixel statistics
# --------------------------------------------------------------------------


def pixel_stats_cpu(state: dict) -> dict:
    feats = _obj_stats_np(np.asarray(state["hema"], np.float64),
                          np.asarray(state["objects"]))
    return {**state, "feat_pixel": feats}


@jax.jit
def _pixel_stats_j(hema, objects):
    return _obj_stats_j(hema.astype(jnp.float32), objects)


def pixel_stats_accel(state: dict) -> dict:
    return {
        **state,
        "feat_pixel": _pixel_stats_j(
            jnp.asarray(state["hema"]), jnp.asarray(state["objects"])
        ),
    }


# --------------------------------------------------------------------------
# gradient statistics
# --------------------------------------------------------------------------


def _conv3_np(img: np.ndarray, k: np.ndarray) -> np.ndarray:
    out = np.zeros_like(img, dtype=np.float32)
    pad = np.pad(img.astype(np.float32), 1, mode="edge")
    for dy in range(3):
        for dx in range(3):
            out += k[dy, dx] * pad[dy : dy + img.shape[0], dx : dx + img.shape[1]]
    return out


def _grad_mag_np(gray: np.ndarray) -> np.ndarray:
    gx = _conv3_np(gray, _SOBEL_X)
    gy = _conv3_np(gray, _SOBEL_Y)
    return np.sqrt(gx * gx + gy * gy)


def gradient_stats_cpu(state: dict) -> dict:
    mag = _grad_mag_np(np.asarray(state["gray"], np.float32))
    feats = _obj_stats_np(mag.astype(np.float64), np.asarray(state["objects"]))
    return {**state, "feat_gradient": feats}


def _conv3_j(img: jnp.ndarray, k: np.ndarray) -> jnp.ndarray:
    pad = jnp.pad(img.astype(jnp.float32), 1, mode="edge")
    out = jnp.zeros_like(img, dtype=jnp.float32)
    for dy in range(3):
        for dx in range(3):
            out = out + k[dy, dx] * jax.lax.dynamic_slice(
                pad, (dy, dx), img.shape
            )
    return out


@jax.jit
def _gradient_stats_j(gray, objects):
    gx = _conv3_j(gray, _SOBEL_X)
    gy = _conv3_j(gray, _SOBEL_Y)
    mag = jnp.sqrt(gx * gx + gy * gy)
    return _obj_stats_j(mag, objects), mag


def gradient_stats_accel(state: dict) -> dict:
    feats, _ = _gradient_stats_j(
        jnp.asarray(state["gray"]), jnp.asarray(state["objects"])
    )
    return {**state, "feat_gradient": feats}


# --------------------------------------------------------------------------
# Haralick (GLCM) texture features — tile level, 8 gray levels
# --------------------------------------------------------------------------

_GLCM_LEVELS = 8


def _quantize_np(gray: np.ndarray) -> np.ndarray:
    lo, hi = gray.min(), gray.max()
    q = (gray - lo) / max(hi - lo, 1e-6) * (_GLCM_LEVELS - 1)
    return q.astype(np.int32)


def _glcm_features(glcm: np.ndarray) -> np.ndarray:
    glcm = glcm / max(glcm.sum(), 1e-9)
    i, j = np.mgrid[0:_GLCM_LEVELS, 0:_GLCM_LEVELS]
    contrast = float((glcm * (i - j) ** 2).sum())
    energy = float((glcm**2).sum())
    homogeneity = float((glcm / (1.0 + np.abs(i - j))).sum())
    entropy = float(-(glcm * np.log(glcm + 1e-12)).sum())
    return np.array([contrast, energy, homogeneity, entropy], np.float32)


def haralick_cpu(state: dict) -> dict:
    q = _quantize_np(np.asarray(state["gray"], np.float32))
    fg = np.asarray(state["mask"])
    glcm = np.zeros((_GLCM_LEVELS, _GLCM_LEVELS), np.float64)
    for dy, dx in ((0, 1), (1, 0)):
        a = q[: q.shape[0] - dy, : q.shape[1] - dx]
        b = q[dy:, dx:]
        m = fg[: q.shape[0] - dy, : q.shape[1] - dx] & fg[dy:, dx:]
        np.add.at(glcm, (a[m], b[m]), 1.0)
        np.add.at(glcm, (b[m], a[m]), 1.0)  # symmetric
    return {**state, "feat_haralick": _glcm_features(glcm)}


@functools.partial(jax.jit, static_argnames=("mxu",))
def _haralick_j(gray: jnp.ndarray, fg: jnp.ndarray, mxu: bool = False):
    lo, hi = gray.min(), gray.max()
    q = ((gray - lo) / jnp.maximum(hi - lo, 1e-6) * (_GLCM_LEVELS - 1)).astype(
        jnp.int32
    )
    glcm = jnp.zeros((_GLCM_LEVELS, _GLCM_LEVELS), jnp.float32)
    h, w = q.shape
    for dy, dx in ((0, 1), (1, 0)):
        # Foreground pairs (q[y, x], q[y+dy, x+dx]) counted in bin 8 a + b;
        # the padded edge pixels pair with nothing.
        pad = ((0, dy), (0, dx))
        b = jnp.pad(q[dy:, dx:], pad)
        both = jnp.pad(fg[: h - dy, : w - dx] & fg[dy:, dx:], pad)
        pairs = _table_sums(q * _GLCM_LEVELS + b, (both,),
                            n_bins=_GLCM_LEVELS ** 2, mxu=mxu, lo_bits=3)
        pairs = pairs.reshape(_GLCM_LEVELS, _GLCM_LEVELS)
        glcm = glcm + pairs + pairs.T
    glcm = glcm / jnp.maximum(glcm.sum(), 1e-9)
    i, j = jnp.mgrid[0:_GLCM_LEVELS, 0:_GLCM_LEVELS]
    contrast = (glcm * (i - j) ** 2).sum()
    energy = (glcm**2).sum()
    homogeneity = (glcm / (1.0 + jnp.abs(i - j))).sum()
    entropy = -(glcm * jnp.log(glcm + 1e-12)).sum()
    return jnp.stack([contrast, energy, homogeneity, entropy])


def haralick_accel(state: dict, mxu: bool = False) -> dict:
    feats = _haralick_j(jnp.asarray(state["gray"]), jnp.asarray(state["mask"]),
                        mxu=mxu)
    return {**state, "feat_haralick": feats}


# --------------------------------------------------------------------------
# Canny-style edges
# --------------------------------------------------------------------------


def canny_edge_cpu(state: dict, lo: float = 20.0, hi: float = 50.0) -> dict:
    mag = _grad_mag_np(np.asarray(state["gray"], np.float32))
    strong, weak = mag >= hi, mag >= lo
    # Hysteresis: reconstruct strong edges within the weak mask.
    from .segmentation import morph_reconstruct_np

    edges = (
        morph_reconstruct_np(
            strong.astype(np.float32) * 255.0, weak.astype(np.float32) * 255.0
        )
        > 0
    )
    s, _, cnt = _seg_sums_np(edges.astype(np.float64), np.asarray(state["objects"]))
    density = (s / np.maximum(cnt, 1)).astype(np.float32)
    return {**state, "feat_canny": density}


@functools.partial(jax.jit, static_argnames=("mxu",))
def _canny_j(gray: jnp.ndarray, objects: jnp.ndarray, lo: float = 20.0,
             hi: float = 50.0, mxu: bool = False):
    from .segmentation import _recon_j  # accel reconstruction

    gx = _conv3_j(gray, _SOBEL_X)
    gy = _conv3_j(gray, _SOBEL_Y)
    mag = jnp.sqrt(gx * gx + gy * gy)
    strong = (mag >= hi).astype(jnp.float32) * 255.0
    weak = (mag >= lo).astype(jnp.float32) * 255.0
    edges = _recon_j(strong, weak) > 0
    s, cnt = _object_sums(objects, (edges,), count=True, mxu=mxu)[:, 1:]
    return s / jnp.maximum(cnt, 1.0)


def canny_edge_accel(state: dict, mxu: bool = False) -> dict:
    density = _canny_j(jnp.asarray(state["gray"]), jnp.asarray(state["objects"]),
                       mxu=mxu)
    return {**state, "feat_canny": density.astype(jnp.float32)}


# --------------------------------------------------------------------------
# morphometry
# --------------------------------------------------------------------------


def morphometry_cpu(state: dict) -> dict:
    objects = np.asarray(state["objects"])
    fg = objects > 0
    # Perimeter pixels: fg with at least one 4-neighbor background.
    pad = np.pad(fg, 1)
    interior = (
        pad[:-2, 1:-1] & pad[2:, 1:-1] & pad[1:-1, :-2] & pad[1:-1, 2:]
    )
    perim = fg & ~interior
    area, _, _ = _seg_sums_np(fg.astype(np.float64), objects)
    per, _, _ = _seg_sums_np(perim.astype(np.float64), objects)
    circ = 4.0 * np.pi * area / np.maximum(per * per, 1.0)
    feats = np.stack([area, per, np.minimum(circ, 4.0)], -1).astype(np.float32)
    return {**state, "feat_morph": feats}


@functools.partial(jax.jit, static_argnames=("mxu",))
def _morphometry_j(objects: jnp.ndarray, mxu: bool = False):
    fg = objects > 0
    pad = jnp.pad(fg, 1)
    interior = (
        pad[:-2, 1:-1] & pad[2:, 1:-1] & pad[1:-1, :-2] & pad[1:-1, 2:]
    )
    perim = fg & ~interior
    # The area is the count: every pixel of an object is foreground.
    per, area = _object_sums(objects, (perim,), count=True, mxu=mxu)[:, 1:]
    circ = 4.0 * jnp.pi * area / jnp.maximum(per * per, 1.0)
    return jnp.stack([area, per, jnp.minimum(circ, 4.0)], -1).astype(jnp.float32)


def morphometry_accel(state: dict, mxu: bool = False) -> dict:
    return {**state, "feat_morph": _morphometry_j(jnp.asarray(state["objects"]),
                                                  mxu=mxu)}
