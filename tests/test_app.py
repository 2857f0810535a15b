"""Image-analysis application: variant agreement, quality, middleware run."""

import numpy as np
import pytest

from repro.app import build_workflow, register_variants, run_tile, synth_tile
from repro.core import (
    ConcreteWorkflow,
    DataChunk,
    LaneSpec,
    Manager,
    ManagerConfig,
    VariantRegistry,
    WorkerRuntime,
)

SIZE = 128


@pytest.fixture(scope="module")
def tile_and_truth():
    return synth_tile(1, size=SIZE, with_truth=True, seed=3)


def test_cpu_accel_variants_agree(tile_and_truth):
    tile, _ = tile_and_truth
    s_cpu = run_tile(tile, "cpu")
    s_acc = run_tile(tile, "accel")
    assert s_cpu["n_objects"] == s_acc["n_objects"]
    m1, m2 = np.asarray(s_cpu["mask"]), np.asarray(s_acc["mask"])
    assert (m1 == m2).mean() > 0.999
    np.testing.assert_allclose(
        np.asarray(s_cpu["feat_haralick"]), np.asarray(s_acc["feat_haralick"]),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        s_cpu["feat_pixel"], np.asarray(s_acc["feat_pixel"]),
        rtol=1e-3, atol=1e-4,
    )


def test_segmentation_quality(tile_and_truth):
    tile, truth = tile_and_truth
    s = run_tile(tile, "cpu")
    m = np.asarray(s["mask"])
    iou = (m & truth.nuclei_mask).sum() / max((m | truth.nuclei_mask).sum(), 1)
    assert iou > 0.5
    assert s["n_objects"] >= truth.n_nuclei * 0.5


def test_middleware_executes_real_pipeline():
    """End to end: Manager -> Workers -> function variants on threads,
    results equal the single-threaded reference."""
    reg = VariantRegistry()
    register_variants(reg)
    wf = build_workflow()
    tiles = [synth_tile(i, size=SIZE, seed=3) for i in range(3)]
    chunks = [DataChunk(i, payload=t) for i, t in enumerate(tiles)]
    cw = ConcreteWorkflow.replicate(wf, chunks)
    workers = []
    for wid in range(2):
        rt = WorkerRuntime(
            wid,
            lanes=(LaneSpec("cpu", 0), LaneSpec("gpu", 0)),
            policy="pats",
            locality=True,
            variant_registry=reg,
        )
        rt.start()
        workers.append(rt)
    mgr = Manager(cw, ManagerConfig(window=2, heartbeat_timeout=60.0))
    for rt in workers:
        mgr.register_worker(rt)
    try:
        assert mgr.run(timeout=300.0)
        done, total = mgr.progress()
        assert done == total == 6  # 3 tiles x 2 stages
        # Spot-check one tile's features against the reference path.
        feat_si = [
            si for si in cw.stage_instances.values()
            if si.stage.name == "features" and si.chunk.chunk_id == 0
        ][0]
        out = mgr.stage_outputs(feat_si.uid)
        want = run_tile(tiles[0], "cpu")
        np.testing.assert_allclose(
            np.asarray(out["haralick"]["feat_haralick"]),
            np.asarray(want["feat_haralick"]),
            rtol=1e-3, atol=1e-4,
        )
    finally:
        for rt in workers:
            rt.stop()


def test_worker_failure_recovery_real_runtime():
    reg = VariantRegistry()
    register_variants(reg)
    wf = build_workflow()
    chunks = [
        DataChunk(i, payload=synth_tile(i, size=64, seed=5)) for i in range(4)
    ]
    cw = ConcreteWorkflow.replicate(wf, chunks)
    w0 = WorkerRuntime(0, lanes=(LaneSpec("cpu", 0),), variant_registry=reg)
    w1 = WorkerRuntime(1, lanes=(LaneSpec("cpu", 0),), variant_registry=reg)
    w0.start()
    w1.start()
    mgr = Manager(cw, ManagerConfig(window=1, heartbeat_timeout=0.5,
                                    poll_interval=0.05))
    mgr.register_worker(w0)
    mgr.register_worker(w1)
    import threading

    killer = threading.Timer(0.2, w1.kill)
    killer.start()
    try:
        assert mgr.run(timeout=300.0)
        done, total = mgr.progress()
        assert done == total
    finally:
        killer.cancel()
        w0.stop()
        w1.stop()


def test_pallas_tpu_variants_registered_and_correct():
    """The kernels bind as 'tpu' function variants; interpret-mode
    execution matches the cpu variant on a lane of that kind."""
    import jax.numpy as jnp

    from repro.app.pipeline import OP_IMPLS, register_variants
    from repro.app.segmentation import (
        morph_open_cpu,
        rbc_detection_cpu,
    )
    from repro.app.tiles import synth_tile
    from repro.core.worker import OpContext
    from repro.core.variants import VariantRegistry
    from repro.core.workflow import DataChunk

    reg = VariantRegistry()
    register_variants(reg, with_pallas=True)
    assert reg.get("color_deconv").supports("tpu")
    assert reg.get("recon_to_nuclei").supports("tpu")
    assert reg.get("fill_holes").supports("tpu")

    tile = synth_tile(2, size=128, seed=9)
    state = morph_open_cpu(rbc_detection_cpu(tile))
    chunk = DataChunk(0, payload=tile)

    # recon_to_nuclei: Pallas vs cpu variant agree on the nuclei mask
    ctx = OpContext(chunk=chunk, inputs={"morph_open": state}, lane_kind="tpu")
    got = reg.get("recon_to_nuclei").implementation("tpu")(ctx)
    want = OP_IMPLS["recon_to_nuclei"][0](state)
    agree = (np.asarray(got["nuclei"]) == np.asarray(want["nuclei"])).mean()
    assert agree > 0.999

    # fill_holes: the Pallas program gives the cpu variant's mask exactly
    at = OP_IMPLS["area_threshold"][0](want)
    ctx_fh = OpContext(chunk=chunk, inputs={"area_threshold": at},
                       lane_kind="tpu")
    got_fh = reg.get("fill_holes").implementation("tpu")(ctx_fh)
    np.testing.assert_array_equal(
        np.asarray(got_fh["mask"]), OP_IMPLS["fill_holes"][0](at)["mask"]
    )

    # color_deconv: hema plane matches to fp tolerance
    state2 = at
    for name in ("fill_holes", "pre_watershed", "watershed", "bwlabel"):
        state2 = OP_IMPLS[name][0](state2)
    ctx2 = OpContext(chunk=chunk, inputs={"bwlabel": state2}, lane_kind="tpu")
    got2 = reg.get("color_deconv").implementation("tpu")(ctx2)
    want2 = OP_IMPLS["color_deconv"][0](state2)
    np.testing.assert_allclose(
        np.asarray(got2["hema"]), np.asarray(want2["hema"]),
        rtol=5e-4, atol=5e-4,
    )


def _ring(mask, top, left, side):
    """Draw a closed one-pixel square ring; return its interior."""
    bottom, right = top + side - 1, left + side - 1
    mask[top, left:right + 1] = mask[bottom, left:right + 1] = True
    mask[top:bottom + 1, left] = mask[top:bottom + 1, right] = True
    hole = np.zeros_like(mask)
    hole[top + 1:bottom, left + 1:right] = True
    return hole


def _holes_case(case, shape):
    """A hand-made mask and the holes ``fill_holes`` must fill in it."""
    h, w = shape
    mask = np.zeros(shape, bool)
    none = np.zeros(shape, bool)
    if case == "closed_ring":
        return mask, _ring(mask, h // 4, w // 4, h // 2)
    if case == "c_open_to_border":
        # A ring whose right side is cut by a corridor to the border.
        _ring(mask, h // 4, w // 4, h // 2)
        mask[h // 2 - 2:h // 2 + 2, w // 4 + h // 2 - 1:] = False
        return mask, none
    if case == "diagonal_leak":
        # Without its corner pixel, the interior meets the outside only
        # across a diagonal: background is 8-connected, so no hole.
        _ring(mask, h // 4, w // 4, h // 2)
        mask[h // 4, w // 4] = False
        return mask, none
    if case == "touching_border":
        # Rings on the top and left edges of the tile and a band down
        # the right edge: the border pixels are foreground.
        hole = _ring(mask, 0, w // 2, 16) | _ring(mask, h // 2, 0, 12)
        mask[:, -3:] = True
        return mask, hole
    if case == "all_foreground":
        return np.ones(shape, bool), none
    assert case == "all_background"
    return mask, none


@pytest.mark.parametrize("shape", [(128, 128), (256, 384)])
@pytest.mark.parametrize("case", ["closed_ring", "c_open_to_border",
                                  "diagonal_leak", "touching_border",
                                  "all_foreground", "all_background"])
def test_fill_holes_pallas_program_equals_xla(case, shape):
    """The Pallas ``fill_holes`` program (interpret mode here) returns
    exactly the XLA program's mask, and both fill what they must."""
    from repro.app.pipeline import _fill_holes_accel_pallas
    from repro.app.segmentation import _fill_holes_accel

    mask, holes = _holes_case(case, shape)
    want = np.asarray(_fill_holes_accel(mask))
    np.testing.assert_array_equal(want, mask | holes)
    np.testing.assert_array_equal(np.asarray(_fill_holes_accel_pallas(mask)),
                                  want)


@pytest.fixture(scope="module")
def segmented_256():
    """A 256² synthetic tile through the cpu segmentation variants."""
    from repro.app.pipeline import _SEG_ORDER, OP_IMPLS

    tile = synth_tile(1, size=256, seed=3)
    state = tile
    for name in _SEG_ORDER:
        state = OP_IMPLS[name][0](state)
    return tile, state


def _count(primitive, fn, *args) -> int:
    """Uses of ``primitive`` in the jaxpr of ``fn(*args)``."""
    import jax

    return str(jax.make_jaxpr(fn)(*args)).count(primitive)


@pytest.mark.parametrize("op,outputs", [
    ("feature_fused", ("feat_pixel", "feat_gradient")),
    ("haralick", ("feat_haralick",)),
    ("canny_edge", ("feat_canny",)),
    ("morphometry", ("feat_morph",)),
])
def test_tpu_feature_variants_match_cpu(segmented_256, op, outputs):
    """The ``tpu`` feature variants (one-hot MXU sums, Pallas interpreted
    here) give the NumPy ``cpu`` variants' ``feat_*`` to 1e-5 of each
    column's largest value, with no scatter-add; the jnp ``accel``
    variants keep ``segment_sum``'s scatter-adds."""
    import functools

    import jax.numpy as jnp

    from repro.app import features as F
    from repro.app.pipeline import OP_IMPLS, _feature_fused_cpu
    from repro.core.worker import OpContext
    from repro.core.workflow import DataChunk

    tile, state = segmented_256
    reg = VariantRegistry()
    register_variants(reg, accel_kind="tpu", with_pallas=True)
    ctx = OpContext(chunk=DataChunk(0, payload=tile),
                    inputs={"bwlabel": state}, lane_kind="tpu")
    got = reg.get(op).implementation("tpu")(ctx)
    if op == "feature_fused":
        want = _feature_fused_cpu(state)
    else:
        want = OP_IMPLS[op][0](OP_IMPLS["color_deconv"][0](state))
    for k in outputs:
        w = np.asarray(want[k], np.float64)
        axis = 0 if w.ndim == 2 else None
        err = (np.abs(np.asarray(got[k], np.float64) - w).max(axis=axis)
               / np.maximum(np.abs(w).max(axis=axis), 1e-30))
        assert np.all(err <= 1e-5), (k, err)

    plane = jnp.asarray(np.asarray(state["gray"], np.float32))
    objects = jnp.asarray(state["objects"])
    fg = jnp.asarray(state["mask"])
    accel, tpu = {
        "feature_fused": ((F._obj_stats_j, plane, objects),
                          (F._pixel_gradient_stats_j, plane, plane, objects)),
        "haralick": ((F._haralick_j, plane, fg),
                     (functools.partial(F._haralick_j, mxu=True), plane, fg)),
        "canny_edge": ((F._canny_j, plane, objects),
                       (functools.partial(F._canny_j, mxu=True), plane,
                        objects)),
        "morphometry": ((F._morphometry_j, objects),
                        (functools.partial(F._morphometry_j, mxu=True),
                         objects)),
    }[op]
    assert _count("scatter-add", *accel) > 0
    assert _count("scatter-add", *tpu) == 0
    assert _count("pallas_call", *tpu) > 0
