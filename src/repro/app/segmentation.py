"""Segmentation-stage operations (paper Fig 1, Table I).

Every operation exists as a *function variant* pair:

* ``*_cpu``  — straightforward NumPy (the OpenCV/Vincent role),
* ``*_accel`` — ``jax.jit`` XLA implementations built from
  ``lax.reduce_window`` / ``lax.while_loop`` primitives (the role of the
  paper's CUDA ports; on TPUs the hot inner loops bind to the Pallas
  kernels in :mod:`repro.kernels`).

State flows through the pipeline as a dict:

    rgb -> gray, fg (foreground mask) -> recon -> mask -> dist
        -> markers -> labels (watershed) -> objects (bwlabel)

The CPU and accelerated variants implement the same fixpoint algorithms
and agree exactly on masks/labels up to label renumbering (asserted in
tests); the paper's CPU/GPU watershed implementations likewise differed
only in internal algorithm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MAX_OBJECTS",
    "to_gray",
    "rbc_detection_cpu", "rbc_detection_accel",
    "morph_open_cpu", "morph_open_accel",
    "recon_to_nuclei_cpu", "recon_to_nuclei_accel",
    "area_threshold_cpu", "area_threshold_accel",
    "fill_holes_cpu", "fill_holes_accel",
    "pre_watershed_cpu", "pre_watershed_accel",
    "watershed_cpu", "watershed_accel",
    "bwlabel_cpu", "bwlabel_accel",
    "label_image_np", "morph_reconstruct_np",
]

# Per-tile object cap of the fixed-shape accelerator features: above
# the most nuclei a generated 4096² tile holds (6,656).
MAX_OBJECTS = 8192


# --------------------------------------------------------------------------
# NumPy building blocks (CPU variants)
# --------------------------------------------------------------------------


def _shift(a: np.ndarray, dy: int, dx: int, fill) -> np.ndarray:
    out = np.full_like(a, fill)
    h, w = a.shape
    ys = slice(max(dy, 0), h + min(dy, 0))
    xs = slice(max(dx, 0), w + min(dx, 0))
    yd = slice(max(-dy, 0), h + min(-dy, 0))
    xd = slice(max(-dx, 0), w + min(-dx, 0))
    out[yd, xd] = a[ys, xs]
    return out


_N8 = [(-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1), (1, 0), (1, 1)]


def _dilate_np(a: np.ndarray) -> np.ndarray:
    out = a.copy()
    for dy, dx in _N8:
        np.maximum(out, _shift(a, dy, dx, a.dtype.type(0) if a.dtype != bool else False), out)
    return out


def _erode_np(a: np.ndarray) -> np.ndarray:
    fill = a.dtype.type(255) if a.dtype == np.uint8 else (
        True if a.dtype == bool else a.dtype.type(np.iinfo(a.dtype).max if np.issubdtype(a.dtype, np.integer) else np.inf)
    )
    out = a.copy()
    for dy, dx in _N8:
        np.minimum(out, _shift(a, dy, dx, fill), out)
    return out


def morph_reconstruct_np(marker: np.ndarray, mask: np.ndarray,
                         max_iters: int = 4096) -> np.ndarray:
    """Vincent's grayscale reconstruction by iterated geodesic dilation."""
    r = np.minimum(marker, mask)
    for _ in range(max_iters):
        nxt = np.minimum(_dilate_np(r), mask)
        if np.array_equal(nxt, r):
            break
        r = nxt
    return r


def label_image_np(fg: np.ndarray, max_iters: int = 65536) -> np.ndarray:
    """Connected components (8-conn) by iterative min-label propagation."""
    h, w = fg.shape
    lab = np.where(fg, np.arange(1, h * w + 1, dtype=np.int32).reshape(h, w), 0)
    big = np.int32(h * w + 2)
    for _ in range(max_iters):
        cand = np.where(fg, lab, big)
        nxt = cand.copy()
        for dy, dx in _N8:
            np.minimum(nxt, _shift(cand, dy, dx, big), nxt)
        nxt = np.where(fg, np.minimum(nxt, cand), 0)
        if np.array_equal(nxt, lab):
            break
        lab = nxt
    return lab


def to_gray(rgb: np.ndarray) -> np.ndarray:
    rgb = np.asarray(rgb, np.float32)
    return 0.299 * rgb[..., 0] + 0.587 * rgb[..., 1] + 0.114 * rgb[..., 2]


# --------------------------------------------------------------------------
# jnp building blocks (accelerator variants)
# --------------------------------------------------------------------------


def _dilate_j(a: jnp.ndarray) -> jnp.ndarray:
    init = (
        jnp.array(-jnp.inf, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else jnp.array(jnp.iinfo(a.dtype).min, a.dtype)
    )
    return jax.lax.reduce_window(a, init, jax.lax.max, (3, 3), (1, 1), "SAME")


def _erode_j(a: jnp.ndarray) -> jnp.ndarray:
    init = (
        jnp.array(jnp.inf, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else jnp.array(jnp.iinfo(a.dtype).max, a.dtype)
    )
    return jax.lax.reduce_window(a, init, jax.lax.min, (3, 3), (1, 1), "SAME")


def _recon_j(marker: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    def cond(state):
        r, changed = state
        return changed

    def body(state):
        r, _ = state
        nxt = jnp.minimum(_dilate_j(r), mask)
        return nxt, jnp.any(nxt != r)

    r0 = jnp.minimum(marker, mask)
    r, _ = jax.lax.while_loop(cond, body, (r0, jnp.array(True)))
    return r


def _label_j(fg: jnp.ndarray) -> jnp.ndarray:
    h, w = fg.shape
    idx = jnp.arange(1, h * w + 1, dtype=jnp.int32).reshape(h, w)
    big = jnp.int32(h * w + 2)
    lab0 = jnp.where(fg, idx, big)

    def cond(state):
        lab, changed = state
        return changed

    def body(state):
        lab, _ = state
        nxt = -jax.lax.reduce_window(
            -lab, jnp.int32(-(h * w + 2)), jax.lax.max, (3, 3), (1, 1), "SAME"
        )
        nxt = jnp.where(fg, jnp.minimum(nxt, lab), big)
        return nxt, jnp.any(nxt != lab)

    lab, _ = jax.lax.while_loop(cond, body, (lab0, jnp.array(True)))
    return jnp.where(fg, lab, 0)


def _gray_j(rgb: jnp.ndarray) -> jnp.ndarray:
    rgb = rgb.astype(jnp.float32)
    return 0.299 * rgb[..., 0] + 0.587 * rgb[..., 1] + 0.114 * rgb[..., 2]


# --------------------------------------------------------------------------
# Pipeline operations — CPU variants
# --------------------------------------------------------------------------


def rbc_detection_cpu(rgb: np.ndarray) -> dict:
    rgb_f = np.asarray(rgb, np.float32)
    ratio = rgb_f[..., 0] / (rgb_f[..., 1] + rgb_f[..., 2] + 1.0)
    rbc = ratio > 1.0
    gray = to_gray(rgb)
    # Candidate foreground: dark (basophilic) pixels, minus RBCs.
    fg = (gray < np.float32(gray.mean()) - 0.35 * gray.std()) & ~rbc
    return {"rgb": np.asarray(rgb), "gray": gray, "fg": fg, "rbc": rbc}


def morph_open_cpu(state: dict) -> dict:
    fg = state["fg"].astype(np.uint8)
    opened = fg
    for _ in range(2):  # erosion radius 2 (disk-approx via 3x3 iterated)
        opened = _erode_np(opened)
    for _ in range(2):
        opened = _dilate_np(opened)
    return {**state, "fg_open": opened.astype(bool)}


def recon_to_nuclei_cpu(state: dict, erosions: int = 8, thresh: float = 25.0) -> dict:
    """Opening-by-reconstruction top-hat: erode past nucleus scale,
    reconstruct the background plateau, threshold the residual domes."""
    gray, fg = state["gray"], state["fg_open"]
    inv = 255.0 - gray  # nuclei bright in inverted image
    marker = inv
    for _ in range(erosions):
        marker = _erode_np(marker)
    recon = morph_reconstruct_np(marker, inv)
    nuclei = ((inv - recon) > thresh) & fg
    return {**state, "recon": recon, "nuclei": nuclei}


def area_threshold_cpu(state: dict, min_area: int = 24, max_area: int = 8192) -> dict:
    lab = label_image_np(state["nuclei"])
    ids, counts = np.unique(lab[lab > 0], return_counts=True)
    keep = ids[(counts >= min_area) & (counts <= max_area)]
    mask = np.isin(lab, keep)
    return {**state, "mask_at": mask}


def fill_holes_cpu(state: dict) -> dict:
    mask = state["mask_at"]
    inv = (~mask).astype(np.uint8) * 255
    border = np.zeros_like(inv)
    border[0, :], border[-1, :], border[:, 0], border[:, -1] = 255, 255, 255, 255
    recon = morph_reconstruct_np(np.minimum(border, inv), inv)
    filled = mask | (recon == 0)
    return {**state, "mask": filled}


def pre_watershed_cpu(state: dict) -> dict:
    mask = state["mask"]
    # Chamfer-ish distance: number of erosions until a pixel disappears.
    dist = np.zeros(mask.shape, np.float32)
    cur = mask.copy()
    for _ in range(64):
        if not cur.any():
            break
        dist += cur
        cur = _erode_np(cur)
    # Markers: regional maxima of smoothed distance.
    d = morph_reconstruct_np(dist - 1.0, dist)
    markers = (dist - d >= 1.0 - 1e-3) & mask
    return {**state, "dist": dist, "markers": markers}


def watershed_cpu(state: dict) -> dict:
    mask, markers, dist = state["mask"], state["markers"], state["dist"]
    lab = label_image_np(markers)
    # Flood outward from markers in decreasing-distance order.
    maxd = int(dist.max()) if mask.any() else 0
    for level in range(maxd, -1, -1):
        grow = mask & (dist >= level)
        for _ in range(256):
            cand = lab.copy()
            frontier = grow & (lab == 0)
            if not frontier.any():
                break
            changed = False
            neigh = np.zeros_like(lab)
            for dy, dx in _N8:
                np.maximum(neigh, _shift(lab, dy, dx, np.int32(0)), neigh)
            adopt = frontier & (neigh > 0)
            if adopt.any():
                cand[adopt] = neigh[adopt]
                changed = True
            lab = cand
            if not changed:
                break
    return {**state, "labels": np.where(mask, lab, 0)}


def bwlabel_cpu(state: dict) -> dict:
    lab = label_image_np(state["labels"] > 0)
    # Compact to 1..n (n capped at MAX_OBJECTS for fixed-shape features).
    ids = np.unique(lab[lab > 0])[:MAX_OBJECTS]
    remap = np.zeros(int(lab.max()) + 1, np.int32)
    remap[ids] = np.arange(1, len(ids) + 1, dtype=np.int32)
    objects = remap[lab]
    return {**state, "objects": objects, "n_objects": int(len(ids))}


# --------------------------------------------------------------------------
# Pipeline operations — accelerator variants (jit'd)
# --------------------------------------------------------------------------


@jax.jit
def _rbc_accel(rgb: jnp.ndarray):
    rgb_f = rgb.astype(jnp.float32)
    ratio = rgb_f[..., 0] / (rgb_f[..., 1] + rgb_f[..., 2] + 1.0)
    rbc = ratio > 1.0
    gray = _gray_j(rgb)
    fg = (gray < gray.mean() - 0.35 * gray.std()) & ~rbc
    return gray, fg, rbc


def rbc_detection_accel(rgb) -> dict:
    gray, fg, rbc = _rbc_accel(jnp.asarray(np.asarray(rgb)))
    return {"rgb": np.asarray(rgb), "gray": gray, "fg": fg, "rbc": rbc}


@jax.jit
def _morph_open_accel(fg: jnp.ndarray):
    x = fg.astype(jnp.uint8)
    for _ in range(2):
        x = _erode_j(x)
    for _ in range(2):
        x = _dilate_j(x)
    return x.astype(bool)


def morph_open_accel(state: dict) -> dict:
    return {**state, "fg_open": _morph_open_accel(jnp.asarray(state["fg"]))}


@jax.jit
def _recon_accel(gray: jnp.ndarray, fg: jnp.ndarray):
    inv = 255.0 - gray
    marker = inv
    for _ in range(8):
        marker = _erode_j(marker)
    recon = _recon_j(marker, inv)
    nuclei = ((inv - recon) > 25.0) & fg
    return recon, nuclei


def recon_to_nuclei_accel(state: dict) -> dict:
    recon, nuclei = _recon_accel(
        jnp.asarray(state["gray"]), jnp.asarray(state["fg_open"])
    )
    return {**state, "recon": recon, "nuclei": nuclei}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _area_threshold_accel(nuclei: jnp.ndarray, min_area: int, max_area: int):
    lab = _label_j(nuclei)
    flat = lab.reshape(-1)
    # Histogram of label sizes via scatter-add onto a dense table.
    counts = jnp.zeros(flat.shape[0] + 2, jnp.int32).at[flat].add(1)
    sz = counts[flat]
    keep = (sz >= min_area) & (sz <= max_area) & (flat > 0)
    return keep.reshape(lab.shape)


def area_threshold_accel(state: dict, min_area: int = 24, max_area: int = 8192) -> dict:
    mask = _area_threshold_accel(jnp.asarray(state["nuclei"]), min_area, max_area)
    return {**state, "mask_at": mask}


def _fill_holes_j(mask: jnp.ndarray, reconstruct) -> jnp.ndarray:
    """Fill the holes of ``mask``: reconstruct the background (255) from
    the image border with ``reconstruct(marker, mask)``; background the
    border cannot reach is a hole.  Any implementation of the 8-connected
    geodesic reconstruction gives the same mask (the fixpoint is unique)."""
    inv = (~mask).astype(jnp.float32) * 255.0
    h, w = mask.shape
    border = jnp.zeros((h, w), jnp.float32)
    border = border.at[0, :].set(255.0).at[-1, :].set(255.0)
    border = border.at[:, 0].set(255.0).at[:, -1].set(255.0)
    recon = reconstruct(jnp.minimum(border, inv), inv)
    return mask | (recon == 0)


@jax.jit
def _fill_holes_accel(mask: jnp.ndarray):
    return _fill_holes_j(mask, _recon_j)


def fill_holes_accel(state: dict) -> dict:
    return {**state, "mask": _fill_holes_accel(jnp.asarray(state["mask_at"]))}


@jax.jit
def _pre_watershed_accel(mask: jnp.ndarray):
    def body(i, carry):
        dist, cur = carry
        dist = dist + cur.astype(jnp.float32)
        nxt = _erode_j(cur.astype(jnp.uint8)).astype(bool)
        return dist, nxt

    dist0 = jnp.zeros(mask.shape, jnp.float32)
    dist, _ = jax.lax.fori_loop(0, 64, body, (dist0, mask))
    d = _recon_j(dist - 1.0, dist)
    markers = (dist - d >= 1.0 - 1e-3) & mask
    return dist, markers


def pre_watershed_accel(state: dict) -> dict:
    dist, markers = _pre_watershed_accel(jnp.asarray(state["mask"]))
    return {**state, "dist": dist, "markers": markers}


@jax.jit
def _watershed_accel(mask: jnp.ndarray, markers: jnp.ndarray, dist: jnp.ndarray):
    lab0 = _label_j(markers)
    maxd = jnp.max(jnp.where(mask, dist, 0.0))

    def level_body(k, lab):
        level = maxd - k.astype(jnp.float32)
        grow = mask & (dist >= level)

        def cond(state):
            lab, changed = state
            return changed

        def body(state):
            lab, _ = state
            neigh = jax.lax.reduce_window(
                lab, jnp.int32(0), jax.lax.max, (3, 3), (1, 1), "SAME"
            )
            adopt = grow & (lab == 0) & (neigh > 0)
            nxt = jnp.where(adopt, neigh, lab)
            return nxt, jnp.any(adopt)

        lab, _ = jax.lax.while_loop(cond, body, (lab, jnp.array(True)))
        return lab

    lab = jax.lax.fori_loop(0, 65, level_body, lab0)
    return jnp.where(mask, lab, 0)


def watershed_accel(state: dict) -> dict:
    labels = _watershed_accel(
        jnp.asarray(state["mask"]), jnp.asarray(state["markers"]),
        jnp.asarray(state["dist"]),
    )
    return {**state, "labels": labels}


@jax.jit
def _bwlabel_accel(fg: jnp.ndarray):
    lab = _label_j(fg)
    flat = lab.reshape(-1)
    present = jnp.zeros(flat.shape[0] + 2, jnp.int32).at[flat].set(1)
    present = present.at[0].set(0)
    rank = jnp.cumsum(present)  # dense renumbering 1..n
    objects = jnp.where(lab > 0, rank[flat].reshape(lab.shape), 0)
    n = rank[-1]
    objects = jnp.where(objects <= MAX_OBJECTS, objects, 0)
    return objects.astype(jnp.int32), jnp.minimum(n, MAX_OBJECTS)


def bwlabel_accel(state: dict) -> dict:
    objects, n = _bwlabel_accel(jnp.asarray(state["labels"] > 0))
    return {**state, "objects": objects, "n_objects": int(n)}
