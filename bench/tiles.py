"""Tile pools for the benchmark's cells, made from ``--seed``.

``synth_tile`` is a frozen copy of the program's tile generator
(``repro.app.tiles.synth_tile`` as of the benchmark's first version), so
that a later change to the program cannot change what the benchmark
feeds it.  Pools are generated in host processes that never import JAX
(the chip belongs to the benchmark's own process) and cached under
``bench/.cache/tiles`` keyed by side, seed and size, so a second run of
a seed in one checkout loads them in well under a second.

A cell whose window outruns its pool cycles it: cycle ``c`` hands out
every pool tile once more, in a seeded order, under one of the eight
rotations and flips of the square tile.  The program keys nothing on
tile content, so a cycled tile is new work.
"""

from __future__ import annotations

import multiprocessing as mp
import os
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

import numpy as np

CACHE = Path(__file__).resolve().parent / ".cache" / "tiles"

#: Tile side whose object scale every larger tile keeps.
SCALE_SIDE = 256


def _disk(h, w, cy, cx, ry, rx, theta):
    """An ellipse inside its bounding box: ``(box, mask over the box)``."""
    r = max(rx, ry)
    y0, y1 = max(int(np.floor(cy - r)), 0), min(int(np.ceil(cy + r)) + 1, h)
    x0, x1 = max(int(np.floor(cx - r)), 0), min(int(np.ceil(cx + r)) + 1, w)
    yy, xx = np.mgrid[y0:y1, x0:x1]
    y, x = yy - cy, xx - cx
    ct, st = np.cos(theta), np.sin(theta)
    u = (ct * x + st * y) / rx
    v = (-st * x + ct * y) / ry
    return (slice(y0, y1), slice(x0, x1)), u * u + v * v <= 1.0


def synth_tile(tile_id: int, size: int = 256, seed: int = 0) -> np.ndarray:
    """An ``(size, size, 3) uint8`` H&E-like tile: pink stroma with
    low-frequency texture, dark nuclei (their count grows with the
    tile's area above ``SCALE_SIDE``), a few red blood cells, noise.
    Seeds of any size are taken modulo 2**32."""
    rng = np.random.default_rng(np.uint32((seed * 100003 + tile_id) % 2**32))
    h = w = size
    scale = min(size, SCALE_SIDE)
    area = max(size // SCALE_SIDE, 1) ** 2
    n_nuclei = int(rng.integers(6, 14)) * max(scale // 128, 1) * area

    base = np.array([231, 180, 202], dtype=np.float32)
    tex = rng.normal(0, 1, (h // 16 + 1, w // 16 + 1)).astype(np.float32)
    tex = np.kron(tex, np.ones((16, 16), np.float32))[:h, :w]
    img = base[None, None, :] + tex[..., None] * np.array([6, 9, 6], np.float32)

    nuclei = np.zeros((h, w), bool)
    placed = 0
    tint = np.array([94, 60, 132], np.float32)
    for _ in range(n_nuclei * 3):
        if placed >= n_nuclei:
            break
        r = rng.uniform(scale * 0.02, scale * 0.05)
        cy, cx = rng.uniform(r, h - r), rng.uniform(r, w - r)
        box, m = _disk(h, w, cy, cx, r * rng.uniform(0.7, 1.0), r,
                       rng.uniform(0, np.pi))
        if (m & nuclei[box]).sum() > 0.25 * m.sum():
            continue
        nuclei[box] |= m
        placed += 1
        depth = rng.uniform(0.55, 0.8)
        if size <= SCALE_SIDE:
            chroma = rng.normal(0, 6, (h, w)).astype(np.float32)[box]
        else:
            chroma = rng.normal(0, 6, m.shape).astype(np.float32)
        sub = img[box]
        sub[m] = sub[m] * (1 - depth) + (tint + chroma[..., None][m]) * depth

    for _ in range(int(rng.integers(0, 4)) * area):
        r = rng.uniform(scale * 0.015, scale * 0.03)
        cy, cx = rng.uniform(r, h - r), rng.uniform(r, w - r)
        box, m = _disk(h, w, cy, cx, r, r, 0.0)
        m &= ~nuclei[box]
        img[box][m] = np.array([198, 60, 54], np.float32)

    img += rng.normal(0, 2.5, img.shape).astype(np.float32)
    return np.clip(img, 0, 255).astype(np.uint8)


def transform(tile: np.ndarray, k: int) -> np.ndarray:
    """The ``k``-th (0..7) rotation/flip of a square tile, contiguous."""
    out = np.rot90(tile, k % 4)
    if k >= 4:
        out = out[:, ::-1]
    return np.ascontiguousarray(out)


def start_pool(n: int, side: int, seed: int, cache: Path | None = CACHE):
    """Start making ``n`` distinct tiles of ``side``² from ``seed`` on
    host processes; returns ``finish() -> (tiles, loaded)``, with
    ``loaded`` true when they came from the cache.  Started before JAX,
    the generation overlaps its initialisation."""
    path = None if cache is None else cache / f"s{side}_n{n}_seed{seed}.npy"
    if path is not None and path.exists():
        return lambda: (np.load(path), True)
    workers = max(1, min(n, len(os.sched_getaffinity(0)) - 1, 8))
    ex = ProcessPoolExecutor(workers, mp_context=mp.get_context("spawn"))
    futures = [ex.submit(synth_tile, i, side, seed) for i in range(n)]

    def finish():
        try:
            tiles = np.stack([f.result() for f in futures])
        finally:
            ex.shutdown()
        if path is not None:
            path.parent.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(f".{os.getpid()}.tmp")
            with open(tmp, "wb") as f:
                np.save(f, tiles)
            os.replace(tmp, path)
        return tiles, False

    return finish
