"""Plain reference of the WSI segmentation + feature pipeline.

Straightforward ``jax.numpy`` over the whole tile, written from the
pipeline's definition (paper §II and Table I, as the program's NumPy
variants state it) and importing nothing of the program.  Every
fixpoint (reconstruction, labeling, flooding) runs to convergence; the
arithmetic is float32 unless ``dtype`` asks for less, which is how the
benchmark builds its control (the same pipeline in bfloat16).

Definitions, per tile of ``rgb`` (uint8):

* ``gray = .299 R + .587 G + .114 B``; ``rbc = R / (G + B + 1) > 1``;
  ``fg = gray < mean(gray) - .35 std(gray)`` and not ``rbc``.
* ``fg_open``: two 3x3 erosions then two 3x3 dilations of ``fg``.
* ``recon``: grayscale reconstruction by dilation of the 8-times-eroded
  ``inv = 255 - gray`` under ``inv``; ``nuclei = inv - recon > 25`` and
  ``fg_open``.
* ``mask_at``: 8-connected components of ``nuclei`` of 24..8192 pixels.
* ``mask``: ``mask_at`` with its holes (background not 8-connected to
  the tile border) filled.
* ``dist``: number of 3x3 erosions (at most 64, the border counting as
  foreground) a pixel of ``mask`` survives; ``markers``: where ``dist``
  stands at least 1 above its reconstruction from ``dist - 1``.
* ``labels``: components of ``markers`` (each labelled by its first
  pixel in raster order) flooded level by level, from the highest
  ``dist`` down to 0, into ``mask``; an unlabelled pixel adopts its
  largest 8-neighbour label.
* ``objects``: components of ``labels > 0`` numbered 1..n in raster
  order of their first pixel, at most ``MAX_OBJECTS``.
* ``hema``, ``eosin``: optical density ``-log10((rgb + 1) / 256)``
  times the inverse of the H&E(+residual) stain matrix.
* per object (rows ``1..MAX_OBJECTS``): ``feat_pixel`` mean, std and
  count of ``hema``; ``feat_gradient`` the same of the Sobel magnitude
  of ``gray`` (edge-replicated border); ``feat_canny`` the share of the
  object's pixels on hysteresis edges (magnitude >= 50, grown through
  magnitude >= 20); ``feat_morph`` area, perimeter (pixels with a
  4-neighbour outside the object's foreground) and ``min(4 pi area /
  perimeter², 4)``.
* ``feat_haralick``: contrast, energy, homogeneity and entropy of the
  symmetric gray-level co-occurrence matrix (8 levels over the tile's
  gray range, offsets (0, 1) and (1, 0), both pixels in ``mask``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MAX_OBJECTS = 8192
#: Ruifrok & Johnston H&E(+residual) stain vectors, rows normalized.
STAINS = np.array([[0.650, 0.704, 0.286],
                   [0.072, 0.990, 0.105],
                   [0.268, 0.570, 0.776]], np.float64)
DECONV = np.linalg.inv(STAINS.T)
SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], np.float64)
LEVELS = 8
#: Dilation steps between two convergence tests of a fixpoint.
STEPS = 8


def _window(a, op, fill):
    """``op`` over each pixel's 3x3 neighbourhood, ``fill`` outside."""
    h, w = a.shape
    p = jnp.pad(a, 1, constant_values=jnp.array(fill, a.dtype))
    out = a
    for dy in range(3):
        for dx in range(3):
            out = op(out, p[dy:dy + h, dx:dx + w])
    return out


def _dilate(a):
    lo = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
    return _window(a, jax.lax.max, lo)


def _erode(a):
    hi = jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).max
    return _window(a, jax.lax.min, hi)


def _fixpoint(step, x0):
    def body(state):
        x, _ = state
        y = jax.lax.fori_loop(0, STEPS, lambda _, v: step(v), x)
        return y, jnp.any(y != x)

    return jax.lax.while_loop(lambda s: s[1], body, (x0, jnp.array(True)))[0]


def _reconstruct(marker, mask):
    return _fixpoint(lambda r: jnp.minimum(_dilate(r), mask),
                     jnp.minimum(marker, mask))


def _label(fg):
    h, w = fg.shape
    big = jnp.int32(h * w + 2)
    idx = jnp.arange(1, h * w + 1, dtype=jnp.int32).reshape(h, w)
    lab = _fixpoint(lambda l: jnp.where(fg, jnp.minimum(_erode(l), l), big),
                    jnp.where(fg, idx, big))
    return jnp.where(fg, lab, 0)


def _bool_erode(m):
    return _erode(m.astype(jnp.uint8)) > 0


def _bool_dilate(m):
    return _dilate(m.astype(jnp.uint8)) > 0


@functools.partial(jax.jit, static_argnames="dtype")
def segment(rgb, dtype=jnp.float32):
    x = rgb.astype(dtype)
    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    gray = (0.299 * r + 0.587 * g + 0.114 * b).astype(dtype)
    rbc = r / (g + b + 1) > 1
    fg = (gray < jnp.mean(gray) - 0.35 * jnp.std(gray)) & ~rbc

    fg_open = _bool_dilate(_bool_dilate(_bool_erode(_bool_erode(fg))))

    inv = (255 - gray).astype(dtype)
    marker = inv
    for _ in range(8):
        marker = _erode(marker)
    recon = _reconstruct(marker, inv)
    nuclei = ((inv - recon) > 25) & fg_open

    lab = _label(nuclei)
    n = lab.size + 2
    size = jnp.zeros(n, jnp.int32).at[lab.ravel()].add(1)[lab]
    mask_at = (lab > 0) & (size >= 24) & (size <= 8192)

    h, w = mask_at.shape
    background = jnp.where(mask_at, 0, 255).astype(dtype)
    edge = jnp.zeros((h, w), bool).at[0].set(True).at[-1].set(True)
    edge = edge.at[:, 0].set(True).at[:, -1].set(True)
    outside = _reconstruct(jnp.where(edge, background, 0).astype(dtype),
                           background)
    mask = mask_at | (outside == 0)

    def count(_, carry):
        d, cur = carry
        return d + cur.astype(dtype), _bool_erode(cur)

    dist, _ = jax.lax.fori_loop(0, 64, count,
                                (jnp.zeros((h, w), dtype), mask))
    markers = (dist - _reconstruct(dist - 1, dist) >= 1 - 1e-3) & mask

    top = jnp.max(jnp.where(mask, dist, 0)).astype(jnp.int32)

    def flood_level(k, lab):
        grow = mask & (dist >= (top - k).astype(dtype))

        def adopt(state):
            lab, _ = state
            neigh = _dilate(lab)
            take = grow & (lab == 0) & (neigh > 0)
            return jnp.where(take, neigh, lab), jnp.any(take)

        return jax.lax.while_loop(lambda s: s[1], adopt,
                                  (lab, jnp.array(True)))[0]

    labels = jax.lax.fori_loop(0, top + 1, flood_level, _label(markers))
    labels = jnp.where(mask, labels, 0)

    comp = _label(labels > 0)
    present = jnp.zeros(comp.size + 2, jnp.int32).at[comp.ravel()].set(1)
    rank = jnp.cumsum(present.at[0].set(0))
    objects = jnp.where(comp > 0, rank[comp], 0)
    objects = jnp.where(objects <= MAX_OBJECTS, objects, 0)
    n_objects = jnp.minimum(rank[-1], MAX_OBJECTS)
    return dict(gray=gray, rbc=rbc, fg=fg, fg_open=fg_open, recon=recon,
                nuclei=nuclei, mask_at=mask_at, mask=mask, dist=dist,
                markers=markers, labels=labels, objects=objects,
                n_objects=n_objects)


def _per_object(values, objects):
    return jax.ops.segment_sum(values.ravel(), objects.ravel(),
                               num_segments=MAX_OBJECTS + 1)[1:]


def _moments(values, objects, dtype):
    s = _per_object(values, objects)
    s2 = _per_object(values * values, objects)
    cnt = _per_object(jnp.ones_like(values), objects)
    safe = jnp.maximum(cnt, 1)
    mean = s / safe
    std = jnp.sqrt(jnp.maximum(s2 / safe - mean * mean, 0))
    return jnp.stack([mean, std, cnt], -1).astype(dtype)


def _sobel_magnitude(gray):
    h, w = gray.shape
    pad = jnp.pad(gray, 1, mode="edge")
    gx = jnp.zeros_like(gray)
    gy = jnp.zeros_like(gray)
    for dy in range(3):
        for dx in range(3):
            win = pad[dy:dy + h, dx:dx + w]
            gx = gx + SOBEL_X[dy, dx] * win
            gy = gy + SOBEL_X[dx, dy] * win
    return jnp.sqrt(gx * gx + gy * gy)


@functools.partial(jax.jit, static_argnames="dtype")
def features(rgb, gray, mask, objects, dtype=jnp.float32):
    x = rgb.astype(dtype)
    od = -jnp.log10((x + 1) / 256)
    hema = sum(DECONV[0, c] * od[..., c] for c in range(3)).astype(dtype)
    eosin = sum(DECONV[1, c] * od[..., c] for c in range(3)).astype(dtype)

    mag = _sobel_magnitude(gray.astype(dtype))
    weak = mag >= 20
    edges = _reconstruct(jnp.where(mag >= 50, 255, 0).astype(dtype),
                         jnp.where(weak, 255, 0).astype(dtype)) > 0
    cnt = _per_object(jnp.ones_like(mag), objects)
    canny = _per_object(edges.astype(dtype), objects) / jnp.maximum(cnt, 1)

    lo, hi = jnp.min(gray), jnp.max(gray)
    q = ((gray - lo) / jnp.maximum(hi - lo, 1e-6) * (LEVELS - 1)).astype(jnp.int32)
    glcm = jnp.zeros((LEVELS, LEVELS), dtype)
    h, w = q.shape
    for dy, dx in ((0, 1), (1, 0)):
        a, b = q[:h - dy, :w - dx].ravel(), q[dy:, dx:].ravel()
        both = (mask[:h - dy, :w - dx] & mask[dy:, dx:]).ravel().astype(dtype)
        glcm = glcm.at[a, b].add(both).at[b, a].add(both)
    p = glcm / jnp.maximum(glcm.sum(), 1e-9)
    i, j = jnp.mgrid[0:LEVELS, 0:LEVELS]
    haralick = jnp.stack([
        (p * (i - j) ** 2).sum(),
        (p * p).sum(),
        (p / (1 + jnp.abs(i - j))).sum(),
        -(p * jnp.log(p + 1e-12)).sum(),
    ]).astype(dtype)

    fg = objects > 0
    pad = jnp.pad(fg, 1)
    interior = pad[:-2, 1:-1] & pad[2:, 1:-1] & pad[1:-1, :-2] & pad[1:-1, 2:]
    area = _per_object(fg.astype(dtype), objects)
    perim = _per_object((fg & ~interior).astype(dtype), objects)
    circ = jnp.minimum(4 * jnp.pi * area / jnp.maximum(perim * perim, 1), 4)
    return dict(hema=hema, eosin=eosin,
                feat_pixel=_moments(hema, objects, dtype),
                feat_gradient=_moments(mag, objects, dtype),
                feat_haralick=haralick,
                feat_canny=canny.astype(dtype),
                feat_morph=jnp.stack([area, perim, circ], -1).astype(dtype))


def run(tile: np.ndarray, dtype=jnp.float32) -> dict:
    """Every output of the pipeline for one tile, as host arrays."""
    rgb = jnp.asarray(tile)
    seg = segment(rgb, dtype=dtype)
    feat = features(rgb, seg["gray"], seg["mask"], seg["objects"],
                    dtype=dtype)
    out = jax.device_get({**seg, **feat})
    out["n_objects"] = int(out["n_objects"])
    return out
