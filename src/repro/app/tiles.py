"""Synthetic whole-slide-image tiles.

Generates H&E-like RGB tiles containing elliptical "nuclei" (dark
basophilic blobs), occasional red-blood-cell discs, pink stroma
background, and sensor noise — enough structure for every pipeline
operation to do real work, deterministic per ``(tile_id, seed)``.
"""

from __future__ import annotations

import numpy as np

__all__ = ["synth_tile", "TileTruth"]


class TileTruth:
    """Ground truth bundled with a synthetic tile (for tests)."""

    def __init__(self, nuclei_mask: np.ndarray, n_nuclei: int,
                 rbc_mask: np.ndarray, nuclei_areas: list[int]):
        self.nuclei_mask = nuclei_mask
        self.n_nuclei = n_nuclei
        self.rbc_mask = rbc_mask
        self.nuclei_areas = nuclei_areas  # pixels of each placed nucleus


#: Tile side whose object scale every larger tile keeps: above it,
#: nuclei and red cells keep their pixel size and their counts grow
#: with the tile's area, as in a slide scanned at one magnification.
SCALE_SIDE = 256


def _disk(h: int, w: int, cy: float, cx: float, ry: float, rx: float,
          theta: float) -> tuple[tuple[slice, slice], np.ndarray]:
    """An ellipse inside its bounding box: ``(box, mask over the box)``.

    The box is the circle of radius ``max(rx, ry)``, so the mask equals
    the full-tile ellipse restricted to it."""
    r = max(rx, ry)
    y0, y1 = max(int(np.floor(cy - r)), 0), min(int(np.ceil(cy + r)) + 1, h)
    x0, x1 = max(int(np.floor(cx - r)), 0), min(int(np.ceil(cx + r)) + 1, w)
    yy, xx = np.mgrid[y0:y1, x0:x1]
    y, x = yy - cy, xx - cx
    ct, st = np.cos(theta), np.sin(theta)
    u = (ct * x + st * y) / rx
    v = (-st * x + ct * y) / ry
    return (slice(y0, y1), slice(x0, x1)), u * u + v * v <= 1.0


def synth_tile(
    tile_id: int,
    size: int = 256,
    n_nuclei: int | None = None,
    seed: int = 0,
    with_truth: bool = False,
):
    """Return an ``(size, size, 3) uint8`` H&E-like tile.

    Costs O(tile + sum of object bounding boxes)."""
    rng = np.random.default_rng(np.uint32(seed * 100003 + tile_id))
    h = w = size
    scale = min(size, SCALE_SIDE)
    area = max(size // SCALE_SIDE, 1) ** 2  # tiles of SCALE_SIDE² per tile
    if n_nuclei is None:
        n_nuclei = int(rng.integers(6, 14)) * max(scale // 128, 1) * area

    # Pink stroma background with low-frequency texture.
    base = np.array([231, 180, 202], dtype=np.float32)
    tex = rng.normal(0, 1, (h // 16 + 1, w // 16 + 1)).astype(np.float32)
    tex = np.kron(tex, np.ones((16, 16), np.float32))[:h, :w]
    img = base[None, None, :] + tex[..., None] * np.array([6, 9, 6], np.float32)

    nuclei = np.zeros((h, w), bool)
    areas: list[int] = []
    tint = np.array([94, 60, 132], np.float32)
    for _ in range(n_nuclei * 3):
        if len(areas) >= n_nuclei:
            break
        r = rng.uniform(scale * 0.02, scale * 0.05)
        cy, cx = rng.uniform(r, h - r), rng.uniform(r, w - r)
        box, m = _disk(h, w, cy, cx, r * rng.uniform(0.7, 1.0), r,
                       rng.uniform(0, np.pi))
        if (m & nuclei[box]).sum() > 0.25 * m.sum():
            continue  # too much overlap
        nuclei[box] |= m
        areas.append(int(m.sum()))
        # Dark purple (hematoxylin) with internal chromatin texture.
        depth = rng.uniform(0.55, 0.8)
        # Tiles up to SCALE_SIDE draw a whole plane of chromatin noise
        # per nucleus (their historical random stream); larger tiles
        # draw only the box.
        if size <= SCALE_SIDE:
            chroma = rng.normal(0, 6, (h, w)).astype(np.float32)[box]
        else:
            chroma = rng.normal(0, 6, m.shape).astype(np.float32)
        sub = img[box]
        sub[m] = sub[m] * (1 - depth) + (tint + chroma[..., None][m]) * depth

    rbc = np.zeros((h, w), bool)
    for _ in range(int(rng.integers(0, 4)) * area):
        r = rng.uniform(scale * 0.015, scale * 0.03)
        cy, cx = rng.uniform(r, h - r), rng.uniform(r, w - r)
        box, m = _disk(h, w, cy, cx, r, r, 0.0)
        m &= ~nuclei[box]
        rbc[box] |= m
        img[box][m] = np.array([198, 60, 54], np.float32)  # eosinophilic red

    img += rng.normal(0, 2.5, img.shape).astype(np.float32)
    tile = np.clip(img, 0, 255).astype(np.uint8)
    if with_truth:
        return tile, TileTruth(nuclei, len(areas), rbc, areas)
    return tile
