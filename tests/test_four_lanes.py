"""One worker driving four chips: four ``tpu`` lanes, lane *i* on
``jax.devices()[i]``, with data-locality assignment (DL) across them.

The pipeline and the chains run in a subprocess with four virtual CPU
devices (Pallas kernels interpreted), as the paper's node runs on a
four-chip host: the fused WSI pipeline at 128² against the NumPy
variants and the plain reference, and chains of small ops that show DL
keeping each chain on the chip that holds it.  In this process: the
lease window derived from a worker's lanes, the DL pop's choices, and
the hand-off counters of a lane's gather.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AbstractWorkflow,
    ConcreteWorkflow,
    DataChunk,
    LaneSpec,
    Manager,
    ManagerConfig,
    Operation,
    Stage,
    VariantRegistry,
    WorkerRuntime,
)
from repro.core.scheduling import ReadyScheduler
from repro.staging import op_key
from repro import transport as T

ROOT = Path(__file__).resolve().parents[1]

PIPELINE_SCRIPT = textwrap.dedent(
    """
    import json, sys, threading
    sys.path.insert(0, ".")
    import jax, numpy as np
    import chip_smoke
    from bench import compare
    from bench.references import wsi_pipeline as reference
    from repro.app import build_workflow, register_variants, run_tile
    from repro.app.tiles import synth_tile
    from repro.core import (ConcreteWorkflow, DataChunk, LaneSpec, Manager,
                            VariantRegistry, WorkerRuntime)

    devices = jax.devices()
    assert len(devices) == 4, devices
    tiles = [synth_tile(i, size=128, seed=15) for i in range(3)]
    reg = register_variants(VariantRegistry(), accel_kind="tpu",
                            with_pallas=True)
    produced = []  # (lane index, devices of an op's output arrays)
    lock = threading.Lock()
    for op in reg.names():
        var = reg.get(op)
        def recording(ctx, _fn=var.impls["tpu"]):
            out = _fn(ctx)
            devs = {str(d) for x in jax.tree_util.tree_leaves(out)
                    if isinstance(x, jax.Array) for d in x.devices()}
            lane = int(threading.current_thread().name.rsplit("tpu", 1)[1])
            with lock:
                produced.append((lane, devs))
            return out
        var.impls["tpu"] = recording
    cw = ConcreteWorkflow.replicate(
        build_workflow(fused=True),
        [DataChunk(i, payload=t) for i, t in enumerate(tiles)])
    mgr = Manager(cw)
    rt = WorkerRuntime(0, lanes=tuple(LaneSpec("tpu", i) for i in range(4)),
                       variant_registry=reg, locality=True)
    rt.start()
    try:
        mgr.register_worker(rt)
        ok = mgr.run(timeout=600.0)
    finally:
        rt.stop()
    clones = mgr._clone_map()
    per_tile = {}
    for si in cw.stage_instances.values():
        if si.uid in clones:
            continue
        t = per_tile.setdefault(si.chunk.chunk_id, {"feat": {}})
        out = mgr.stage_outputs(si.uid)
        if si.stage.name == "segmentation":
            t["seg"] = out["bwlabel"]
        else:
            t["feat"] = out
    shim = type("Run", (), {"tiles": per_tile})
    numpy_bad, ref_numbers = [], []
    for i, tile in enumerate(tiles):
        numpy_bad += [f"tile {i}: {b}"
                      for b in chip_smoke.compare(run_tile(tile, "cpu"), shim, i)]
        got = dict(per_tile[i]["seg"])
        for out in per_tile[i]["feat"].values():
            got.update(out)
        ref_numbers.append(compare.tile_numbers(got, reference.run(tile)))
    st = rt.stats()
    lane_devices = {}
    for lane, devs in produced:
        lane_devices.setdefault(lane, set()).update(devs)
    print(json.dumps({
        "ok": ok, "errors": [repr(e) for _, e in rt.errors],
        "window": mgr._workers[0].window,
        "numpy_bad": numpy_bad, "reference": compare.worst(ref_numbers),
        "host_fallbacks": st["host_fallbacks"],
        "lane_devices": {str(k): sorted(v) for k, v in lane_devices.items()},
        "devices": [str(d) for d in devices],
    }))
    """
)

CHAIN_SCRIPT = textwrap.dedent(
    """
    import json, sys, threading, time
    import jax, jax.numpy as jnp
    from repro.core import (AbstractWorkflow, ConcreteWorkflow, DataChunk,
                            LaneSpec, Manager, Operation, Stage,
                            VariantRegistry, WorkerRuntime)

    OPS = [f"s{k}" for k in range(8)]

    def run(locality):
        ran = {}  # (chunk, op) -> lane thread
        lock = threading.Lock()
        reg = VariantRegistry()
        for name in OPS:
            def impl(ctx, _name=name):
                x = (jnp.zeros((8, 128), jnp.float32) if not ctx.inputs
                     else jnp.asarray(ctx.sole_input()) + 1.0)
                with lock:
                    ran[(ctx.chunk.chunk_id, _name)] = (
                        threading.current_thread().name)
                return x
            reg.register(name, "tpu", impl)
        wf = AbstractWorkflow.chain(
            "w", [Stage.chain("chain", [Operation(n) for n in OPS])])
        cw = ConcreteWorkflow.replicate(wf, [DataChunk(i) for i in range(3)])
        mgr = Manager(cw)
        rt = WorkerRuntime(0, lanes=tuple(LaneSpec("tpu", i) for i in range(4)),
                           variant_registry=reg, locality=locality)
        rt.start()
        try:
            mgr.register_worker(rt)
            beat = rt.on_heartbeat
            def slow_beat(wid):
                # The committing lane lingers after releasing the op's
                # successor, as a lane does in its Manager callbacks:
                # an idle lane is woken while it holds the inputs.
                time.sleep(0.02)
                beat(wid)
            rt.on_heartbeat = slow_beat
            ok = mgr.run(timeout=120.0)
        finally:
            rt.stop()
        snap = rt.metrics.snapshot()
        lanes = {c: sorted({ran[(c, n)] for n in OPS}) for c in range(3)}
        return {
            "ok": ok, "errors": [repr(e) for _, e in rt.errors],
            "lanes_per_chain": {str(c): v for c, v in lanes.items()},
            "handoffs": sum(v for k, v in snap.items()
                            if k.endswith(".handoffs")),
            "handoff_bytes": sum(v for k, v in snap.items()
                                 if k.endswith(".handoff_bytes")),
            "deferrals": snap["scheduler.reuse_deferrals"],
            "hits": snap["scheduler.reuse_hits"],
        }

    print(json.dumps({"locality": run(True), "plain": run(False)}))
    """
)


def _four_devices(script: str) -> dict:
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=900, env=env, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def pipeline():
    return _four_devices(PIPELINE_SCRIPT)


@pytest.fixture(scope="module")
def chains():
    return _four_devices(CHAIN_SCRIPT)


# -- (a) the fused pipeline on four lanes ------------------------------------


def test_four_lanes_run_the_pipeline_as_numpy_does(pipeline):
    """Masks agree on 99.9 % of pixels, object counts are equal, and
    every output is within ``chip_smoke``'s tolerances."""
    assert pipeline["ok"] and not pipeline["errors"], pipeline["errors"]
    assert pipeline["host_fallbacks"] == 0
    assert pipeline["numpy_bad"] == []


def test_four_lanes_meet_the_plain_references_limits(pipeline):
    from bench import compare

    lim = compare.limits("gbm4k-4chip.batch")
    numbers = {**pipeline["reference"], "failed": 0, "host_fallbacks": 0,
               "worker_errors": 0}
    assert compare.judge(numbers, lim), numbers


def test_each_lane_outputs_on_its_own_device(pipeline):
    assert pipeline["window"] == 8
    devices = pipeline["devices"]
    assert sorted(pipeline["lane_devices"]) == ["0", "1", "2", "3"]
    for lane, devs in pipeline["lane_devices"].items():
        assert devs == [devices[int(lane)]], (lane, devs)


# -- (b) DL keeps a chain on the chip that holds it --------------------------


def test_dl_keeps_each_chain_on_its_lane(chains):
    run = chains["locality"]
    assert run["ok"] and not run["errors"], run["errors"]
    # No op ran on another lane than its predecessor's: one lane a
    # chain, and no input gathered from off a lane.
    assert all(len(v) == 1 for v in run["lanes_per_chain"].values()), run
    assert run["handoffs"] == 0 and run["handoff_bytes"] == 0
    assert run["hits"] == 3 * 7
    # Idle lanes were woken while a holder committed, and left it the op.
    assert run["deferrals"] > 0


def test_without_dl_chains_hand_off_between_lanes(chains):
    run = chains["plain"]
    assert run["ok"] and not run["errors"], run["errors"]
    assert run["deferrals"] == 0
    assert run["handoffs"] > 0
    assert any(len(v) > 1 for v in run["lanes_per_chain"].values()), run
    # (d) Every hand-off is one downloaded (8, 128) float32 output.
    assert run["handoff_bytes"] == run["handoffs"] * 8 * 128 * 4


# -- (c) the lease window follows the worker's lanes -------------------------


def _chain_workflow(n_chunks=1, ops=("a", "b")):
    wf = AbstractWorkflow.chain(
        "w", [Stage.chain("chain", [Operation(n) for n in ops])])
    return ConcreteWorkflow.replicate(wf, [DataChunk(i) for i in range(n_chunks)])


@pytest.mark.parametrize("lanes, configured, window", [
    ((("tpu", 0),), None, 4),
    ((("cpu", 0), ("cpu", 1)), None, 4),
    ((("tpu", 0), ("cpu", 0), ("cpu", 1)), None, 6),
    (tuple(("tpu", i) for i in range(4)), None, 8),
    (tuple(("tpu", i) for i in range(4)), 3, 3),
    ((("tpu", 0),), 6, 6),
])
def test_lease_window_follows_the_lanes(lanes, configured, window):
    mgr = Manager(_chain_workflow(), ManagerConfig(window=configured))
    rt = WorkerRuntime(0, lanes=tuple(LaneSpec(k, i) for k, i in lanes))
    assert rt.lane_count == len(lanes)
    mgr.register_worker(rt)  # not started: nothing is leased to run
    st = mgr._workers[0]
    assert st.window == window
    assert mgr._window_for_locked(0, st) == window


def test_bus_worker_reports_its_lanes():
    mgr = Manager(_chain_workflow())
    endpoint = T.ManagerEndpoint(mgr, T.InprocBus())
    rt = WorkerRuntime(0, lanes=tuple(LaneSpec("cpu", i) for i in range(5)))
    try:
        client = T.WorkerClient(rt, T.InprocBus(), endpoint.address)
        assert endpoint.wait_workers(1, timeout=30.0)
        assert endpoint.proxies[0].lane_count == 5
        assert mgr._workers[0].window == 10
        assert client.window == 10
    finally:
        endpoint.bus.close()


# -- the DL pop across lanes --------------------------------------------------


def _ops(n_chunks):
    """Op instances of ``n_chunks`` chains a -> b: (firsts, seconds)."""
    cw = _chain_workflow(n_chunks)
    firsts, seconds = [], []
    for si in cw.stage_instances.values():
        by_name = {oi.op.name: oi for oi in si.op_instances}
        firsts.append(by_name["a"])
        seconds.append(by_name["b"])
    return firsts, seconds


def test_dl_pop_prefers_ops_no_sibling_holds():
    firsts, seconds = _ops(2)
    s = ReadyScheduler(locality=True)
    s.push(seconds[0])           # its input is on an idle sibling
    s.push(firsts[1])            # a new chain: nobody holds anything
    held = {firsts[0].uid}
    assert s.pop("tpu", set(), [(held, set())]) is firsts[1]
    # Only the held op is left and its holder is idle: left to it.
    assert s.pop("tpu", set(), [(held, set())]) is None
    assert s.stats.reuse_deferrals == 1
    # The holder is finishing the producer's commit: still left to it.
    assert s.pop("tpu", set(), [(held, {firsts[0].uid})]) is None
    # The holder runs another op: work-conserving hand-off.
    assert s.pop("tpu", set(), [(held, {999})]) is seconds[0]
    assert s.stats.reuse_deferrals == 2 and s.stats.reuse_misses == 1


def test_dl_pop_reuse_wins_over_unheld_ops():
    firsts, seconds = _ops(2)
    s = ReadyScheduler(locality=True)
    s.push(firsts[1])
    s.push(seconds[0])
    assert s.pop("tpu", {firsts[0].uid}, [(set(), set())]) is seconds[0]
    assert s.stats.reuse_hits == 1


@pytest.mark.parametrize("locality, siblings", [(False, True), (True, False)])
def test_one_lane_or_no_dl_pops_in_queue_order(locality, siblings):
    firsts, seconds = _ops(2)
    s = ReadyScheduler(locality=locality)
    s.push(seconds[0])
    s.push(firsts[1])
    other = [({firsts[0].uid}, set())] if siblings else None
    assert s.pop("tpu", set(), other) is seconds[0]
    assert s.stats.reuse_deferrals == 0


# -- (d) the hand-off counters of a gather ------------------------------------


def test_handoff_bytes_are_the_inputs_gathered_from_off_the_lane():
    cw = _chain_workflow(ops=("a", "b", "c"))
    (si,) = cw.stage_instances.values()
    a, b, c = sorted(si.op_instances, key=lambda oi: oi.op.name)
    rt = WorkerRuntime(0, lanes=(LaneSpec("tpu", 0),), locality=True)
    rt.start()
    try:
        lane = rt._lanes[0]
        host = {"x": np.ones((16, 16), np.float32), "n": 3}
        rt.store.put(op_key(a.uid), host)       # the host tier's copy
        inputs, nbytes = rt._gather_inputs(lane, b)
        assert nbytes == 16 * 16 * 4 and inputs["a"]["n"] == 3
        # Now resident on the lane: a second gather moves nothing.
        assert rt._gather_inputs(lane, b)[1] == 0
        lane.memory.put(b.uid, {"y": jnp.zeros((4, 8), jnp.int32)})
        assert rt._gather_inputs(lane, c)[1] == 0
        snap = rt.metrics.snapshot()
        assert snap["worker.lane.tpu0.handoffs"] == 1
        assert snap["worker.lane.tpu0.handoff_bytes"] == 16 * 16 * 4
    finally:
        rt.stop()
