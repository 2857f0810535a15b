"""A whole run of each cell on the CPU at a small tile side, with the
timed path broken underneath: ``correct`` must come out false for each
fault a cell can have, and true with nothing broken.  (One chip, so no
exchange between chips to leave out.)"""

import numpy as np
import pytest

from bench import run

SIDE = 128
SECONDS = 2.0


def _wrap(registry, op, change):
    var = registry.get(op)
    for kind, impl in list(var.impls.items()):
        def broken(ctx, _impl=impl):
            return change(ctx, _impl)
        broken.__name__ = impl.__name__
        var.impls[kind] = broken


def unchanged_state(registry):
    """``morph_open`` returns its input state unchanged (no opening)."""
    def change(ctx, impl):
        out = impl(ctx)
        return {**out, "fg_open": np.asarray(out["fg"])}
    _wrap(registry, "morph_open", change)


def half_left_out(registry):
    """The per-object hematoxylin statistics are taken over the top half
    of the tile only."""
    def change(ctx, impl):
        from repro.core import OpContext

        inputs = {}
        for name, state in ctx.inputs.items():
            objects = np.array(state["objects"])
            objects[objects.shape[0] // 2:] = 0
            inputs[name] = {**state, "objects": objects}
        out = impl(OpContext(chunk=ctx.chunk, inputs=inputs,
                             lane_kind=ctx.lane_kind))
        original = next(iter(ctx.inputs.values()))["objects"]
        return {**out, "objects": original}
    for op in ("feature_fused", "pixel_stats"):
        _wrap(registry, op, change)


def answer_altered(registry):
    """``bwlabel`` reports one object more than it labelled."""
    def change(ctx, impl):
        out = impl(ctx)
        return {**out, "n_objects": int(out["n_objects"]) + 1}
    _wrap(registry, "bwlabel", change)


CELLS = ("gbm4k.batch",)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", (unchanged_state, half_left_out,
                                   answer_altered))
def test_broken_path_is_not_correct(cell, fault):
    result = run.measure(cell, 2**31 + 7, SECONDS, False,
                         require_chip=False, side=SIDE, registry_hook=fault)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_path_is_correct(cell):
    result = run.measure(cell, 2**31 + 7, SECONDS, False,
                         require_chip=False, side=SIDE)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0


def test_refuses_without_a_chip():
    with pytest.raises(run.Refused):
        run.measure("gbm4k.batch", 1, SECONDS, False)
