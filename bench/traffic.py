"""The one traffic generator: every mix is a data file in ``traffic/``.

A mix file holds parameters only: a bag of tasks in which ``backlog``
tiles are kept outstanding, the next one submitted when one completes,
over ``pool`` distinct tiles generated from the seed (``bench.tiles``).
"""

from __future__ import annotations

import itertools
import json
import random
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@dataclass(frozen=True)
class Item:
    """One tile to submit: pool index, rotation/flip and a chunk id
    unique in the run."""

    chunk_id: int
    pool_index: int
    transform: int


def closed(mix: dict, seed: int) -> Iterator[Item]:
    """Endless stream: each cycle hands out every pool tile once, in a
    seeded order, under a rotation/flip that differs from cycle to
    cycle (cycle ``c`` uses transform ``(c + offset) % 8``)."""
    rng = random.Random(seed * 1_000_003 + 17)
    n = int(mix["pool"])
    offset = rng.randrange(8)
    chunk = 0
    for cycle in itertools.count():
        order = list(range(n))
        rng.shuffle(order)
        for i in order:
            yield Item(chunk, i, (cycle + offset) % 8)
            chunk += 1
