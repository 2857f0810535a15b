"""The control of ``correct``: the plain reference computed in
bfloat16, put in the program's place, fails every cell's limits, while
the float32 reference against itself passes them.  (On the chip the
same comparison runs at each cell's own tile side; see
``bench/calibrate.py``.)"""

import jax.numpy as jnp
import pytest

from bench import compare
from bench.references import wsi_pipeline as reference
from bench.tiles import synth_tile

CELLS = ("gbm4k.batch",)
ACCOUNTING = {"failed": 0, "host_fallbacks": 0, "worker_errors": 0}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", (1, 2, 3))
def test_bfloat16_control_is_not_correct(cell, seed):
    tile = synth_tile(0, size=256, seed=seed)
    want = reference.run(tile)
    control = reference.run(tile, dtype=jnp.bfloat16)
    numbers = {**compare.tile_numbers(control, want), **ACCOUNTING}
    assert not compare.judge(numbers, compare.limits(cell)), numbers


@pytest.mark.parametrize("cell", CELLS)
def test_reference_against_itself_is_correct(cell):
    tile = synth_tile(0, size=128, seed=5)
    want = reference.run(tile)
    numbers = {**compare.tile_numbers(dict(want), want), **ACCOUNTING}
    assert numbers["plane_mismatch"] == 0 and numbers["value_gap"] == 0
    assert compare.judge(numbers, compare.limits(cell))
