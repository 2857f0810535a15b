"""Lane phases, transfer counters and the coordinator's spans on one JAX
lane (CPU backend): the six phase counters tile the lane thread's time,
``worker.d2h_bytes`` counts the device bytes downloaded, the Manager
roots one trace per chunk on the batch path with a ``stage:queued`` span
for the pending wait, and nothing is recorded without a tracer."""

import time

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
from repro.core.worker import LANE_PHASES
from repro.telemetry import Tracer, use_context

SIDE = 64
PLANE_BYTES = SIDE * SIDE * 4
LANE = "tpu0"


def _registry(names, sleep=0.0):
    """Each op returns one new float32 device plane (and a host scalar)."""

    def step(ctx):
        if sleep:
            time.sleep(sleep)
        if not ctx.inputs:
            base = jnp.full((SIDE, SIDE), float(ctx.chunk.chunk_id), jnp.float32)
        else:
            base = next(iter(ctx.inputs.values()))["plane"]
        return {"plane": base + 1.0, "host": np.int64(1)}

    reg = VariantRegistry()
    for name in names:
        reg.register(name, "tpu", step)
    return reg


def _workflow(stages):
    """``stages``: op names per stage, one chained stage after another."""
    return AbstractWorkflow.chain(
        "phases",
        [Stage.chain(f"s{i}", [Operation(n) for n in ops])
         for i, ops in enumerate(stages)],
    )


def _phase_ns(rt):
    snap = rt.metrics.snapshot()
    return {p: snap[f"worker.lane.{LANE}.{p}_ns"] for p in LANE_PHASES}


def _run_stream(stages, n_chunks, *, tracer=None, window=4, sleep=0.0):
    """Chunks through the batch path (open_stream / submit_instances)."""
    names = [n for ops in stages for n in ops]
    cw = ConcreteWorkflow(_workflow(stages))
    mgr = Manager(cw, ManagerConfig(window=window, backup_tasks=False),
                  tracer=tracer)
    rt = WorkerRuntime(0, lanes=(LaneSpec("tpu", 0),),
                       variant_registry=_registry(names, sleep), tracer=tracer)
    t_start = time.perf_counter_ns()
    rt.start()
    mgr.register_worker(rt)
    mgr.open_stream()
    submitted = {}
    for c in range(n_chunks):
        sis = cw.instantiate(DataChunk(c))
        before = time.time()
        mgr.submit_instances(sis)
        submitted[c] = (before, time.time())
    assert mgr.close_stream(timeout=60.0)
    rt.stop()
    t_stop = time.perf_counter_ns()
    assert not rt.errors
    return cw, mgr, rt, submitted, t_stop - t_start


def test_phase_counters_tile_the_lane_thread():
    cw, mgr, rt, _, wall_ns = _run_stream([["a", "b", "c"]], 6, sleep=0.02)
    ns = _phase_ns(rt)
    total = sum(ns.values())
    assert 0.99 * wall_ns <= total <= wall_ns
    for phase in ("gather", "dispatch", "sync", "d2h", "commit"):
        assert ns[phase] > 0, phase
    # 18 ops of >= 20 ms each: the dispatch phase holds the sleeps.
    assert ns["dispatch"] >= 18 * 20e6
    busy = rt.stats()["lane_busy"][LANE]
    assert busy == pytest.approx((total - ns["wait"]) * 1e-9)


def test_d2h_bytes_count_the_device_leaves_downloaded():
    cw, mgr, rt, _, _ = _run_stream([["a", "b"], ["c"]], 4)
    snap = rt.metrics.snapshot()
    # Every op's output is written back: one plane each, the host
    # scalar moves nothing.
    n_ops = 3 * 4
    assert snap["worker.d2h_bytes"] == n_ops * PLANE_BYTES
    assert snap["worker.d2h_calls"] == n_ops
    assert rt.stats()["downloads"] == n_ops
    # Host planes are uploaded by the ops themselves: no array moved
    # between devices.
    assert rt.stats()["uploads"] == 0


def test_manager_roots_one_trace_per_chunk_and_spans_the_pending_wait():
    tracer = Tracer("t", sample_rate=1.0, seed=1)
    n_chunks, op_s = 3, 0.05
    cw, mgr, rt, submitted, _ = _run_stream(
        [["a"], ["b"]], n_chunks, tracer=tracer, window=1, sleep=op_s)
    spans = tracer.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    stage_chunk = {si.uid: si.chunk.chunk_id
                   for si in cw.stage_instances.values()}
    op_chunk = {oi.uid: oi.chunk.chunk_id for oi in cw.op_instances.values()}
    traces = {}
    for s in by_name["stage:queued"] + by_name["stage:lease"]:
        traces.setdefault(stage_chunk[s["args"]["uid"]], set()).add(s["trace"])
    for s in by_name["op:a"] + by_name["op:b"]:
        traces.setdefault(op_chunk[s["args"]["uid"]], set()).add(s["trace"])
    assert sorted(traces) == list(range(n_chunks))
    assert all(len(t) == 1 for t in traces.values())
    assert len({t for ts in traces.values() for t in ts}) == n_chunks

    queued = {s["args"]["uid"]: s for s in by_name["stage:queued"]}
    leased = {s["args"]["uid"]: s for s in by_name["stage:lease"]}
    assert set(queued) == set(leased) == set(cw.stage_instances)
    for uid, q in queued.items():
        # The pending wait ends where the lease begins.
        assert q["ts"] + q["dur"] == pytest.approx(leased[uid]["ts"], abs=2e-3)
    firsts = [si for si in cw.stage_instances.values() if not si.deps]
    for si in firsts:
        before, after = submitted[si.chunk.chunk_id]
        assert before <= queued[si.uid]["ts"] <= after
    # One lease at a time, first in first out: the last chunk's first
    # stage waits behind the first stages of the chunks before it.
    last = max(firsts, key=lambda si: si.chunk.chunk_id)
    assert queued[last.uid]["dur"] >= (n_chunks - 1) * op_s * 0.9

    # Every op has one span per phase, on the lane's row.
    for oi in cw.op_instances.values():
        mine = sorted((s for s in spans
                       if s["args"].get("uid") == oi.uid
                       and s["name"].startswith("op:")),
                      key=lambda s: s["ts"])
        assert len(mine) == 1 and mine[0]["args"]["synced"] is True
    phase_names = [s["name"] for s in spans if s["cat"] == "lane"]
    n_ops = len(cw.op_instances)
    for name in ("lane:gather", "lane:sync", "lane:commit", "lane:d2h",
                 "lane:wait"):
        assert phase_names.count(name) == n_ops, name
    d2h = by_name["lane:d2h"]
    assert all(s["args"]["bytes"] == PLANE_BYTES for s in d2h)
    assert {s["tid"] for s in spans if s["cat"] in ("lane", "op")} == {LANE}


def test_no_tracer_records_nothing_and_counters_still_count(monkeypatch):
    recorded = []
    monkeypatch.setattr(Tracer, "record_span",
                        lambda self, *a, **k: recorded.append(a))
    cw, mgr, rt, _, _ = _run_stream([["a", "b"]], 3)
    assert recorded == []
    ns = _phase_ns(rt)
    assert ns["dispatch"] > 0 and ns["sync"] > 0
    assert rt.metrics.snapshot()["worker.d2h_bytes"] == 6 * PLANE_BYTES


def test_chained_op_is_not_synced():
    tracer = Tracer("t", sample_rate=1.0, seed=2)
    cw = ConcreteWorkflow.replicate(_workflow([["a", "b"]]), [DataChunk(0)])
    rt = WorkerRuntime(0, lanes=(LaneSpec("tpu", 0),), chaining=True,
                       variant_registry=_registry(["a", "b"]), tracer=tracer)
    rt.start()
    try:
        with use_context(tracer.start_trace()):
            for si in cw.stage_instances.values():
                rt.submit_stage(si)
        assert rt.drain(timeout=30.0)
    finally:
        rt.stop()
    assert not rt.errors
    spans = tracer.spans()
    ops = {s["name"]: s for s in spans if s["name"].startswith("op:")}
    assert ops["op:a"]["args"]["synced"] is False
    assert ops["op:b"]["args"]["synced"] is True
    syncs = [s for s in spans if s["name"] == "lane:sync"]
    assert len(syncs) == 1
    # The sync span of the sink op follows its dispatch, not op a's.
    assert syncs[0]["ts"] >= ops["op:b"]["ts"]
    assert rt.stats()["chain_deferred"] == 1
    # Only the sink's plane came down.
    assert rt.metrics.snapshot()["worker.d2h_bytes"] == PLANE_BYTES
