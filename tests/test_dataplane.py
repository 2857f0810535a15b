"""Coordinator-bypass data plane: worker-to-worker region transfer,
predictive push of sink outputs, holder-cache invalidation, segmented
bulk frames, byte-keyed journal compaction, the simulator's adaptive
micro-batching."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import repro.transport as T
from repro.core import LaneSpec, Manager, ManagerConfig, WorkerRuntime
from repro.core.workflow import ConcreteWorkflow
from repro.staging import DirectoryService, StagingConfig
from repro.staging.agent import StagingAgent
from repro.staging.store import RegionStore, op_key
from repro.staging.tiers import HostTier
from repro.transport.demo import (
    expected_combine,
    fanin_concrete,
    fanin_registry,
    fanin_workflow,
)


# --------------------------------------------------------------------------
# StagingAgent: direct dial, holder cache, invalidation
# --------------------------------------------------------------------------


def _wait(predicate, timeout: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.005)
    return False


def test_agent_direct_dial_bypasses_relay():
    """Keys whose holder resolves are pulled via dial (worker-to-worker);
    unresolved keys fall back to the Manager relay fetch."""
    region = np.ones((8, 8), np.float32)
    dialed: list = []
    relayed: list = []

    def resolve(keys):
        # Only even uids have a known sibling holder.
        return [(1, "addr-1") if k[1] % 2 == 0 else None for k in keys]

    def dial(holder, keys):
        assert holder == (1, "addr-1")
        dialed.extend(keys)
        return [region for _ in keys]

    def fetch_batch(keys):
        relayed.extend(keys)
        return [region for _ in keys]

    store = RegionStore([HostTier()])
    agent = StagingAgent(
        store, resolve=resolve, dial=dial, fetch_batch=fetch_batch
    )
    keys = [op_key(i) for i in range(6)]
    agent.request_prefetch(keys)
    agent.start()
    assert _wait(lambda: all(k in store for k in keys))
    agent.stop()
    assert sorted(k[1] for k in dialed) == [0, 2, 4]
    assert sorted(k[1] for k in relayed) == [1, 3, 5]
    assert agent.direct_keys == 3 and agent.relay_keys == 3
    assert agent.direct_bytes == 3 * region.nbytes


def test_agent_stale_holder_invalidation_and_fallback():
    """A region_drop invalidation purges the cached holder; a dial that
    finds the region already spilled (stale holder) degrades to the
    relay and drops the cache entry — never a wrong answer."""
    region = np.ones((4, 4), np.float32)
    resolves: list = []
    holder_has_key = {op_key(0): True}

    def resolve(keys):
        resolves.append(list(keys))
        return [(1, "addr-1") for _ in keys]

    def dial(holder, keys):
        return [region if holder_has_key.get(k) else None for k in keys]

    fetched: list = []

    def fetch(key):
        fetched.append(key)
        return region

    store = RegionStore([HostTier()])
    agent = StagingAgent(store, resolve=resolve, dial=dial, fetch=fetch)
    # Prime the cache.
    assert agent.stage_now(op_key(0))
    assert agent._holders == {op_key(0): (1, "addr-1")}
    # The holder spills the region; the Manager broadcast invalidates.
    agent.invalidate_holder(op_key(0), 1)
    assert agent._holders == {}
    assert agent.holder_invalidations == 1
    # Stale-holder race: cache says worker 1 holds key 2, but by dial
    # time the region is gone there -> relay fallback, cache cleaned.
    holder_has_key[op_key(2)] = False
    store.discard(op_key(0))
    assert agent.stage_now(op_key(2))
    assert fetched == [op_key(2)]
    assert agent.direct_misses == 1
    assert op_key(2) not in agent._holders
    # Worker-wide invalidation purges every entry naming the worker.
    agent._holders = {op_key(5): (1, "a"), op_key(6): (2, "b")}
    agent.invalidate_worker(1)
    assert agent._holders == {op_key(6): (2, "b")}


def test_agent_expect_push_defers_then_pulls():
    """An expected push defers the pull; if the push never lands the key
    re-enters the queue after the grace period (lost-push backstop)."""
    region = np.ones((4, 4), np.float32)
    fetched: list = []

    def fetch(key):
        fetched.append(key)
        return region

    store = RegionStore([HostTier()])
    agent = StagingAgent(store, fetch=fetch, push_grace=0.15)
    agent.expect_push([op_key(1), op_key(2)])
    assert agent.pushes_expected == 2
    # A push lands for key 1 before the deadline...
    store.put(op_key(1), region)
    agent.start()
    # Requests for deferred keys are skipped while inflight.
    agent.request_prefetch([op_key(1), op_key(2)])
    # ...key 2's push never arrives: pulled after the grace period.
    assert _wait(lambda: op_key(2) in store, timeout=10.0)
    agent.stop()
    assert fetched == [op_key(2)]  # key 1 was never re-pulled
    assert agent.pushes_landed == 1


# --------------------------------------------------------------------------
# SocketBus: segmented bulk frames
# --------------------------------------------------------------------------


def test_socketbus_segments_large_payloads():
    """A payload above max_frame_bytes rides chunked ``seg`` messages
    and reassembles bit-for-bit; small control calls keep working while
    bulk is in flight."""
    received: dict = {}

    def sink(peer, payload):
        received["arr"] = payload["arr"]
        return payload["arr"].shape

    server = T.SocketBus(max_frame_bytes=64 * 1024)
    address = server.serve({"sink": sink, "echo": lambda p, x: x})
    client = T.SocketBus(max_frame_bytes=64 * 1024)
    peer = client.connect(address)
    big = np.arange(300_000, dtype=np.float64)  # ~2.4 MB >> 64 KB frames
    assert tuple(peer.call("sink", {"arr": big}, timeout=30.0)) == big.shape
    np.testing.assert_array_equal(received["arr"], big)
    assert peer.sent_segments > 1  # the request went out chunked
    assert peer.call("echo", 7) == 7  # control path still healthy
    peer.close()
    server.close()
    client.close()


# --------------------------------------------------------------------------
# End-to-end over the bus: zero coordinator-relayed region bytes
# --------------------------------------------------------------------------

N_CHUNKS = 4
EXPECTED = sorted(expected_combine(i) for i in range(N_CHUNKS))


def _combine_outputs(mgr: Manager, cw) -> list[float]:
    clones = mgr._clone_map()  # noqa: SLF001
    return sorted(
        mgr.stage_outputs(si.uid).get("combine")
        for si in cw.stage_instances.values()
        if si.stage.name == "combine" and si.uid not in clones
    )


def _run_fanin_over_bus(
    bus_factory, *, push: bool, window: int = 1, n_chunks: int = N_CHUNKS
):
    cw = fanin_concrete(n_chunks)
    mgr = Manager(
        cw,
        ManagerConfig(
            window=window,
            locality_aware=True,
            backup_tasks=False,
            heartbeat_timeout=120.0,
            predictive_push=push,
        ),
    )
    endpoint = T.ManagerEndpoint(mgr, bus_factory())
    workers, clients = [], []
    for wid in range(2):
        rt = WorkerRuntime(
            wid,
            lanes=(LaneSpec("cpu", 0),),
            variant_registry=fanin_registry(),
            staging=StagingConfig(),
        )
        rt.start()
        workers.append(rt)
        clients.append(T.WorkerClient(rt, bus_factory(), endpoint.address))
    try:
        assert endpoint.wait_workers(2, timeout=30.0)
        assert mgr.run(timeout=120.0)
        expected = sorted(expected_combine(i) for i in range(n_chunks))
        assert _combine_outputs(mgr, cw) == expected
        return mgr, endpoint, workers, clients
    finally:
        for rt in workers:
            rt.stop()
        endpoint.bus.close()


@pytest.mark.parametrize("bus_cls", [T.InprocBus, T.SocketBus])
def test_worker_to_worker_transfer_zero_relay(bus_cls):
    """Happy path: every cross-worker region byte flows worker-to-worker
    (direct dial); the coordinator relays ~nothing."""
    mgr, endpoint, workers, clients = _run_fanin_over_bus(
        bus_cls, push=False
    )
    assert endpoint.relay_bytes == 0
    assert mgr.relay_bytes == 0
    direct = sum(rt.agent.direct_keys for rt in workers)
    served = sum(c.served_regions for c in clients)
    assert direct > 0 and served > 0  # the fan-in forces a cross edge


@pytest.mark.parametrize("bus_cls", [T.InprocBus, T.SocketBus])
def test_predictive_push_lands_sink_outputs(bus_cls):
    """With predictive push, the completing worker pushes sink outputs
    to the predicted next holder; the coordinator still relays nothing
    and the run stays correct.

    One chunk makes the push deterministic: produce_a (fast) leaves
    worker 0 idle, so when produce_b completes on worker 1 the combine
    is predicted onto worker 0 and b's output must be pushed there."""
    mgr, endpoint, workers, clients = _run_fanin_over_bus(
        bus_cls, push=True, n_chunks=1
    )
    assert endpoint.relay_bytes == 0
    pushed = sum(c.pushes for c in clients)
    ingested = sum(rt.push_ingested for rt in workers)
    assert mgr.push_directives > 0
    assert pushed > 0 and ingested > 0


def test_push_then_crash_failover_pushed_replica_survives(tmp_path):
    """A pushed replica is journaled (region_staged -> directory.record):
    after a coordinator crash the rehydrated Manager still knows the
    push target holds the region and can refetch from it."""
    release = threading.Event()
    reg = fanin_registry()

    def gated_combine(ctx):
        assert release.wait(timeout=60.0)
        a = np.asarray(ctx.inputs["produce_a"])
        b = np.asarray(ctx.inputs["produce_b"])
        return float(a.sum() + b.sum())

    reg.register("combine", "cpu", gated_combine)  # overrides the stock impl
    cw = fanin_concrete(1)
    journal = str(tmp_path / "manager.wal")

    workers = []
    for wid in range(2):
        rt = WorkerRuntime(
            wid,
            lanes=(LaneSpec("cpu", 0),),
            variant_registry=reg,
            staging=StagingConfig(),
        )
        rt.start()
        workers.append(rt)
    try:
        # -- phase 1: produce_a on w0, produce_b (slow) on w1; at b's
        # completion the combine is predicted onto w0 (it holds a), so
        # b's output is PUSHED w1 -> w0; combine wedges on the gate.
        mgr1 = Manager(
            cw,
            ManagerConfig(
                window=1,
                locality_aware=True,
                backup_tasks=False,
                heartbeat_timeout=120.0,
                predictive_push=True,
                journal_path=journal,
            ),
        )
        endpoint1 = T.ManagerEndpoint(mgr1, T.InprocBus())
        clients1 = [
            T.WorkerClient(rt, T.InprocBus(), endpoint1.address)
            for rt in workers
        ]
        assert endpoint1.wait_workers(2, timeout=30.0)
        assert not mgr1.run(timeout=2.0)  # combine is gated: must time out
        b_sink = next(
            oi.uid
            for si in cw.stage_instances.values()
            if si.stage.name == "produce_b"
            for oi in si.op_instances
        )
        assert sum(c.pushes for c in clients1) >= 1
        assert workers[0].push_ingested >= 1
        holders = mgr1.directory.holders(op_key(b_sink))
        assert 0 in holders and 1 in holders  # producer + pushed replica
        mgr1.directory.close()  # the coordinator dies
        endpoint1.bus.close()

        # -- phase 2: rehydrate; the pushed replica came back from the
        # journal, and a fresh cluster finishes the workflow off it.
        mgr2 = Manager(
            cw,
            ManagerConfig(
                window=1,
                locality_aware=True,
                backup_tasks=False,
                heartbeat_timeout=120.0,
                predictive_push=True,
                journal_path=journal,
            ),
        )
        assert 1 in mgr2.directory.holders(op_key(b_sink))
        assert 0 in mgr2.directory.holders(op_key(b_sink))
        endpoint2 = T.ManagerEndpoint(mgr2, T.InprocBus())
        clients2 = [
            T.WorkerClient(rt, T.InprocBus(), endpoint2.address)
            for rt in workers
        ]
        assert endpoint2.wait_workers(2, timeout=30.0)
        # Release only after the workers are re-bridged onto the new
        # coordinator: the wedged combine's completion must reach mgr2.
        release.set()
        assert mgr2.run(timeout=60.0)
        assert _combine_outputs(mgr2, cw) == [expected_combine(0)]
        # The rehydrated coordinator can refetch the pushed bytes from
        # the replica the journal named (not just the producer).
        value = mgr2._fetch_region(op_key(b_sink))  # noqa: SLF001
        assert value is not None
        endpoint2.bus.close()
        del clients2
    finally:
        release.set()
        for rt in workers:
            rt.stop()


# --------------------------------------------------------------------------
# journal compaction by bytes
# --------------------------------------------------------------------------


def test_journal_checkpoint_triggers_on_bytes(tmp_path):
    import os

    path = str(tmp_path / "dir.wal")
    svc = DirectoryService(path, snapshot_bytes=2048)
    for i in range(300):
        svc.record(i % 4, op_key(i), 10 * (i + 1))
    # The live journal tail never grows far past the byte budget...
    assert os.path.getsize(path) <= 2048 + 256
    assert os.path.exists(path + ".snap")
    svc.close()
    # ...so a rehydrate replays a bounded tail yet restores everything.
    svc2 = DirectoryService(path, snapshot_bytes=2048)
    for i in range(300):
        assert svc2.holders(op_key(i)) == {i % 4: 10 * (i + 1)}
    assert svc2.replayed < 100


@pytest.mark.slow
def test_journal_rehydrate_bounded_at_fig14_scale(tmp_path):
    """fig14-scale lease stream (36,848 tiles): with the byte-keyed
    checkpoint the rehydrate replays a bounded tail and stays fast."""
    path = str(tmp_path / "dir.wal")
    svc = DirectoryService(path, snapshot_bytes=512 * 1024)
    n = 36_848
    for uid in range(n):
        svc.note_pending(uid)
        svc.note_lease(uid, uid % 100)
        svc.record(uid % 100, op_key(uid), 48 << 20)
        svc.note_complete(uid)
    svc.close()

    t0 = time.perf_counter()
    svc2 = DirectoryService(path, snapshot_bytes=512 * 1024)
    rehydrate_s = time.perf_counter() - t0
    assert len(svc2.completed) == n
    assert svc2.outstanding() == []
    # Replay is bounded by the byte budget, not the 4*36k event stream.
    assert svc2.replayed < 20_000
    assert rehydrate_s < 10.0


# --------------------------------------------------------------------------
# the simulator's adaptive micro-batch sizing (cost_model.optimal_micro_batch)
# --------------------------------------------------------------------------


def test_sim_adaptive_batch_respects_budget():
    """The simulated dispatcher's per-op cap follows the cost model's
    latency-budget curve: slow ops stop batching, fast ops batch deep —
    instead of SimConfig.micro_batch being one constant for all."""
    from repro.core.simulator import (
        ClusterSim,
        SimConfig,
        make_tiles,
        run_simulation,
        segmentation_feature_workflow,
    )

    cfg = SimConfig(
        policy="pats", micro_batch=16, launch_overhead=0.05,
        adaptive_batch=True, batch_latency_budget=0.4,
    )
    cw = ConcreteWorkflow.replicate(
        segmentation_feature_workflow(), make_tiles(4)
    )
    sim = ClusterSim(cw, cfg)
    by_name = {}
    for oi in cw.op_instances.values():
        by_name.setdefault(oi.op.name, oi)
    # morph_open: ~0.58 accel-seconds/instance > budget -> no batching.
    assert sim._op_batchable(by_name["morph_open"]) == 1  # noqa: SLF001
    # haralick: ~0.06 accel-seconds -> several launches fit the budget.
    b = sim._op_batchable(by_name["haralick"])  # noqa: SLF001
    assert 2 <= b <= cfg.micro_batch
    # Static mode keeps the config constant for every batchable op.
    sim_static = ClusterSim(
        ConcreteWorkflow.replicate(segmentation_feature_workflow(), make_tiles(4)),
        SimConfig(policy="pats", micro_batch=16, launch_overhead=0.05),
    )
    assert sim_static._op_batchable(by_name["morph_open"]) == 16  # noqa: SLF001
    # End-to-end: the adaptive run still completes and batches.
    r = run_simulation(40, cfg)
    assert r.completed_ok and r.batches > 0


# --------------------------------------------------------------------------
# simulator: direct vs relay link model, push hides first touch
# --------------------------------------------------------------------------


def _sim_fanin_builder():
    from repro.core.workflow import AbstractWorkflow, Operation, Stage

    return AbstractWorkflow(
        "fanin",
        (
            Stage.single(Operation("rbc_detection")),
            Stage.single(Operation("morph_open")),
            Stage.single(Operation("haralick")),
        ),
        (("rbc_detection", "haralick"), ("morph_open", "haralick")),
    )


def test_sim_direct_transfer_beats_coordinator_relay():
    from repro.core.simulator import SimConfig, run_simulation

    base = dict(
        n_nodes=4, staging=True, staging_locality=False, window=4,
        stage_output_mb=256.0, interconnect_gb_s=2.0,
    )
    direct = run_simulation(
        40, SimConfig(**base, direct_transfer=True),
        workflow_builder=_sim_fanin_builder,
    )
    relay = run_simulation(
        40, SimConfig(**base, direct_transfer=False),
        workflow_builder=_sim_fanin_builder,
    )
    assert direct.completed_ok and relay.completed_ok
    # Accounting: all cross bytes direct in one mode, relayed in the other.
    assert direct.relay_region_bytes == 0 and direct.direct_region_bytes > 0
    assert relay.direct_region_bytes == 0 and relay.relay_region_bytes > 0
    # The shared coordinator NIC (2x bytes) can only be slower.
    assert direct.tiles_per_second >= relay.tiles_per_second


def test_sim_predictive_push_at_least_matches_pull():
    from repro.core.simulator import SimConfig, run_simulation

    base = dict(
        n_nodes=2, staging=True, staging_locality=True, window=2,
        stage_output_mb=256.0, interconnect_gb_s=2.0,
    )
    pull = run_simulation(
        30, SimConfig(**base, predictive_push=False),
        workflow_builder=_sim_fanin_builder,
    )
    push = run_simulation(
        30, SimConfig(**base, predictive_push=True),
        workflow_builder=_sim_fanin_builder,
    )
    assert pull.completed_ok and push.completed_ok
    assert push.pushes > 0
    # Parity bar: pushing the predicted first touch never loses.
    assert push.tiles_per_second >= pull.tiles_per_second


# --------------------------------------------------------------------------
# real OS processes (slow tier)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_multiprocess_zero_relay_and_push(tmp_path):
    """Acceptance: Manager + 2 worker OS processes over SocketBus with
    the data plane on — region bytes flow worker-to-worker (zero
    coordinator relay) and predictive pushes land."""
    cw = fanin_concrete(N_CHUNKS)
    mgr = Manager(
        cw,
        ManagerConfig(
            window=1,
            locality_aware=True,
            backup_tasks=False,
            heartbeat_timeout=120.0,
            predictive_push=True,
        ),
    )
    endpoint = T.ManagerEndpoint(mgr, T.SocketBus())
    procs = [
        T.spawn_worker(
            endpoint.address,
            T.WorkerSpec(
                worker_id=wid,
                registry="repro.transport.demo:fanin_registry",
            ),
        )
        for wid in range(2)
    ]
    try:
        assert endpoint.wait_workers(2, timeout=120.0)
        assert mgr.run(timeout=120.0)
        assert _combine_outputs(mgr, cw) == EXPECTED
        assert endpoint.relay_bytes == 0
        stats = [p.stats() for p in endpoint.proxies.values()]
        moved_direct = sum(
            s.get("prefetch", {}).get("direct_keys", 0)
            + s.get("push_ingested", 0)
            for s in stats
        )
        assert moved_direct > 0
    finally:
        endpoint.close()
        for p in procs:
            p.join(timeout=15.0)
    assert all(p.exitcode == 0 for p in procs)
