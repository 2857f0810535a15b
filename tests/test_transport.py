"""Cluster transport layer: codec, buses, endpoints, batched prefetch,
directory journal, and (slow) real multiprocess SocketBus runs."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import repro.transport as T
from repro.core import LaneSpec, Manager, ManagerConfig, WorkerRuntime
from repro.staging import DirectoryService, StagingConfig
from repro.staging.agent import StagingAgent
from repro.staging.store import RegionStore, op_key
from repro.staging.tiers import HostTier
from repro.transport.demo import demo_concrete, demo_registry, expected_consume

N_CHUNKS = 6


# --------------------------------------------------------------------------
# codec
# --------------------------------------------------------------------------


def test_codec_roundtrip_arrays_and_graphs():
    codec = T.default_codec()
    arr = np.arange(24, dtype=np.float32).reshape(4, 6)
    payload = {
        "arr": arr,
        "key": ("op", 42),
        "nested": ({"x": 1}, b"raw", None, 2.5),
        "pickled": {1, 2, 3},  # msgpack can't: exercises pickle fallback
    }
    out = codec.decode(codec.encode(payload))
    np.testing.assert_array_equal(out["arr"], arr)
    assert out["arr"].dtype == np.float32
    assert out["key"] == ("op", 42)  # tuples survive (use_list=False)
    assert out["nested"][1] == b"raw"
    assert out["pickled"] == {1, 2, 3}
    assert codec.pickle_fallbacks >= 1


def test_codec_custom_entry_wins_over_pickle():
    class Point:
        def __init__(self, x, y):
            self.x, self.y = x, y

    codec = T.default_codec()
    codec.register(
        T.Codec(
            "pt",
            lambda v: isinstance(v, Point),
            lambda v: {"x": v.x, "y": v.y},
            lambda d: Point(d["x"], d["y"]),
        )
    )
    out = codec.decode(codec.encode([Point(3, 4)]))[0]
    assert (out.x, out.y) == (3, 4)
    assert codec.pickle_fallbacks == 0


# --------------------------------------------------------------------------
# buses
# --------------------------------------------------------------------------


def _echo_handlers(log):
    def echo(peer, payload):
        log.append(payload)
        return payload

    def boom(peer, payload):
        raise ValueError("kaboom")

    return {"echo": echo, "boom": boom}


@pytest.mark.parametrize("bus_cls", [T.InprocBus, T.SocketBus])
def test_bus_call_notify_and_remote_error(bus_cls):
    log: list = []
    server = bus_cls()
    address = server.serve(_echo_handlers(log))
    client = bus_cls() if bus_cls is T.SocketBus else server
    peer = client.connect(address)
    assert peer.call("echo", {"a": 1}) == {"a": 1}
    peer.notify("echo", "fire-and-forget")
    with pytest.raises(T.RemoteError):
        peer.call("boom")
    deadline = time.monotonic() + 5.0
    while len(log) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert log[0] == {"a": 1} and log[1] == "fire-and-forget"
    peer.close()
    server.close()


@pytest.mark.parametrize("bus_cls", [T.InprocBus, T.SocketBus])
def test_untraced_handler_ignores_a_trace_envelope(bus_cls):
    """A traced sender's envelope reaches a handler that does not trace
    as the bare payload; one marked ``traced`` sees the envelope."""
    from repro.transport.bus import TRACE_ENVELOPE

    seen: list = []

    def plain(peer, payload):
        seen.append(payload)
        return payload

    def traced(peer, payload):
        seen.append(payload)
        return "ok"

    traced.traced = True
    server = bus_cls()
    address = server.serve({"plain": plain, "traced": traced})
    client = bus_cls() if bus_cls is T.SocketBus else server
    peer = client.connect(address)
    env = {TRACE_ENVELOPE: {"t": "a" * 16, "s": "b" * 16}, "p": {"x": 1}}
    assert peer.call("plain", env) == {"x": 1}
    assert peer.call("traced", env) == "ok"
    assert seen == [{"x": 1}, env]
    peer.close()
    server.close()


def test_socketbus_ordered_delivery_and_coalescing():
    received: list[int] = []
    release = threading.Event()

    def slow_then_log(peer, payload):
        release.wait(timeout=10.0)
        received.append(payload)

    server = T.SocketBus()
    address = server.serve({"log": slow_then_log})
    client = T.SocketBus()
    peer = client.connect(address)
    for i in range(50):
        peer.notify("log", i)
    release.set()
    deadline = time.monotonic() + 10.0
    while len(received) < 50 and time.monotonic() < deadline:
        time.sleep(0.01)
    # Per-peer ordered delivery: notifies arrive in send order.
    assert received == list(range(50))
    # Coalescing: 50 messages queued behind a blocked dispatcher ride
    # far fewer frames than messages.
    assert client.frames_sent < client.messages_sent
    peer.close()
    server.close()
    client.close()


def test_socketbus_concurrent_calls_match_replies():
    def double(peer, payload):
        time.sleep(0.002)
        return payload * 2

    server = T.SocketBus()
    address = server.serve({"double": double})
    client = T.SocketBus()
    peer = client.connect(address)
    results: dict[int, int] = {}

    def worker(i):
        results[i] = peer.call("double", i)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
    assert results == {i: 2 * i for i in range(16)}
    peer.close()
    server.close()
    client.close()


def test_peer_close_fails_pending_and_fires_disconnect():
    dropped = []
    server = T.SocketBus()
    address = server.serve({}, on_disconnect=lambda p: dropped.append(p))
    client = T.SocketBus()
    peer = client.connect(address)
    peer.close()
    with pytest.raises(T.BusClosedError):
        peer.call("anything")
    deadline = time.monotonic() + 5.0
    while not dropped and time.monotonic() < deadline:
        time.sleep(0.01)
    assert dropped, "server never observed the disconnect"
    server.close()
    client.close()


# --------------------------------------------------------------------------
# Manager/Worker over the bus: identical results on every backend
# --------------------------------------------------------------------------


def _run_direct() -> list[float]:
    cw = demo_concrete(N_CHUNKS)
    mgr = Manager(cw, ManagerConfig(window=2, locality_aware=True))
    workers = []
    for wid in range(2):
        rt = WorkerRuntime(
            wid, lanes=(LaneSpec("cpu", 0),),
            variant_registry=demo_registry(), staging=StagingConfig(),
        )
        rt.start()
        workers.append(rt)
        mgr.register_worker(rt)
    try:
        assert mgr.run(timeout=60.0)
        return _consume_outputs(mgr, cw)
    finally:
        for rt in workers:
            rt.stop()


def _run_over_bus(bus_factory) -> list[float]:
    cw = demo_concrete(N_CHUNKS)
    mgr = Manager(cw, ManagerConfig(window=2, locality_aware=True))
    endpoint = T.ManagerEndpoint(mgr, bus_factory())
    workers = []
    for wid in range(2):
        rt = WorkerRuntime(
            wid, lanes=(LaneSpec("cpu", 0),),
            variant_registry=demo_registry(), staging=StagingConfig(),
        )
        rt.start()
        workers.append(rt)
        T.WorkerClient(rt, bus_factory(), endpoint.address)
    try:
        assert endpoint.wait_workers(2, timeout=30.0)
        assert mgr.run(timeout=60.0)
        return _consume_outputs(mgr, cw)
    finally:
        for rt in workers:
            rt.stop()
        endpoint.bus.close()


def _consume_outputs(mgr: Manager, cw) -> list[float]:
    clones = mgr._clone_map()  # noqa: SLF001
    return sorted(
        mgr.stage_outputs(si.uid).get("consume")
        for si in cw.stage_instances.values()
        if si.stage.name == "consume" and si.uid not in clones
    )


EXPECTED = sorted(expected_consume(i) for i in range(N_CHUNKS))


def test_manager_over_inproc_bus_matches_direct():
    assert _run_direct() == EXPECTED
    assert _run_over_bus(T.InprocBus) == EXPECTED


def test_manager_over_socket_bus_matches_direct():
    assert _run_over_bus(T.SocketBus) == EXPECTED


# --------------------------------------------------------------------------
# batched staging fetches (satellite)
# --------------------------------------------------------------------------


def _agent_fixture(fetch_batch=None, fetch=None):
    store = RegionStore([HostTier()])
    landed: list = []
    agent = StagingAgent(
        store,
        fetch=fetch,
        fetch_batch=fetch_batch,
        max_batch=16,
        on_staged=lambda key, n: landed.append(key),
    )
    return store, agent, landed


def test_prefetch_coalesces_keys_into_batched_pulls():
    calls: list[list] = []

    def fetch_batch(keys):
        calls.append(list(keys))
        return [np.ones(4) for _ in keys]

    store, agent, landed = _agent_fixture(fetch_batch=fetch_batch)
    keys = [op_key(i) for i in range(12)]
    agent.request_prefetch(keys)  # enqueued before the thread starts
    agent.start()
    deadline = time.monotonic() + 10.0
    while len(landed) < 12 and time.monotonic() < deadline:
        time.sleep(0.01)
    agent.stop()
    assert sorted(k[1] for k in landed) == list(range(12))
    assert all(op_key(i) in store for i in range(12))
    # >= 2x fewer round-trips than keys (the acceptance bar); with the
    # queue pre-filled the coalescer should do far better than that.
    assert agent.fetch_calls <= len(keys) // 2
    assert agent.batched_keys == 12
    assert sum(len(c) for c in calls) == 12


def test_prefetch_falls_back_to_per_key_without_batch_source():
    fetched: list = []

    def fetch(key):
        fetched.append(key)
        return np.ones(2)

    store, agent, landed = _agent_fixture(fetch=fetch)
    agent.request_prefetch([op_key(i) for i in range(5)])
    agent.start()
    deadline = time.monotonic() + 10.0
    while len(landed) < 5 and time.monotonic() < deadline:
        time.sleep(0.01)
    agent.stop()
    assert agent.fetch_calls == 5  # one round-trip per key
    assert agent.batched_keys == 0


# --------------------------------------------------------------------------
# directory journal (failover-surviving placement state)
# --------------------------------------------------------------------------


def test_directory_service_replays_journal(tmp_path):
    path = str(tmp_path / "dir.wal")
    svc = DirectoryService(path)
    svc.record(0, op_key(1), 100)
    svc.record(1, op_key(1), 100)
    svc.record(1, op_key(2), 50)
    svc.evict(0, op_key(1))
    svc.note_pending(7)
    svc.note_lease(8, 1)
    svc.note_lease(9, 0)
    svc.note_complete(9)
    svc.close()

    svc2 = DirectoryService(path)
    assert svc2.holders(op_key(1)) == {1: 100}
    assert svc2.holders(op_key(2)) == {1: 50}
    assert svc2.completed == {9}
    assert set(svc2.outstanding()) == {7, 8}
    assert svc2.replayed > 0


def test_directory_service_snapshot_bounds_replay(tmp_path):
    path = str(tmp_path / "dir.wal")
    svc = DirectoryService(path, snapshot_every=10)
    for i in range(25):
        svc.record(i % 3, op_key(i), 10 * (i + 1))
    svc.note_lease(100, 2)
    svc.close()

    svc2 = DirectoryService(path, snapshot_every=10)
    # Snapshot + tail replay reconstructs everything...
    for i in range(25):
        assert svc2.holders(op_key(i)) == {i % 3: 10 * (i + 1)}
    assert set(svc2.outstanding()) == {100}
    # ...but the journal tail replayed is bounded by the checkpoint.
    assert svc2.replayed < 25


def test_journal_repairs_torn_tail_on_reopen(tmp_path):
    """A half-written final line (crash mid-append) must be truncated on
    reopen: appending onto the fragment would corrupt it AND make the
    next replay discard every entry written after the restart."""
    path = str(tmp_path / "dir.wal")
    svc = DirectoryService(path)
    svc.record(0, op_key(1), 10)
    svc.record(1, op_key(2), 20)
    svc.close()
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"e":"rec","w":2,"k"')  # torn: no newline, bad JSON

    svc2 = DirectoryService(path)  # reopen repairs the tail...
    svc2.record(2, op_key(3), 30)  # ...so this append starts clean
    svc2.close()
    svc3 = DirectoryService(path)
    assert svc3.holders(op_key(1)) == {0: 10}
    assert svc3.holders(op_key(2)) == {1: 20}
    assert svc3.holders(op_key(3)) == {2: 30}  # post-restart entry kept
    assert svc3.replayed == 3  # the torn fragment is gone, not replayed


def test_directory_service_drop_worker_survives_restart(tmp_path):
    path = str(tmp_path / "dir.wal")
    svc = DirectoryService(path)
    svc.record(0, op_key(1), 10)
    svc.record(1, op_key(1), 10)
    svc.note_lease(5, 0)
    svc.drop_worker(0)
    svc.close()
    svc2 = DirectoryService(path)
    assert svc2.holders(op_key(1)) == {1: 10}
    assert svc2.outstanding() == []  # worker 0's lease dropped with it


# --------------------------------------------------------------------------
# calibrated tier budgets (satellite)
# --------------------------------------------------------------------------


def test_staging_budgets_from_calibration(tmp_path):
    from repro.core import calibration as cal
    from repro.core.simulator import SimConfig, run_simulation

    cfg = StagingConfig.from_calibration(window=15, stage_output_mb=48.0)
    node = cal.KEENELAND_NODE
    # Budget is a real fraction of node RAM...
    assert cfg.host_budget_bytes <= node.host_ram_gb * 2**30
    # ...and never below the simulator's staged working set (window
    # leases, input+output region each): soft budgets stay soft.
    assert cfg.host_budget_bytes >= 2 * 15 * 48 * 2**20
    disk = StagingConfig.from_calibration(disk_dir=str(tmp_path))
    assert disk.disk_budget_bytes is not None
    assert disk.disk_budget_bytes <= node.scratch_disk_gb * 2**30
    # Validated against the simulator's staging=True cost model: the
    # modeled run moves stage regions of exactly the size the budget
    # was derived for.
    r = run_simulation(
        12, SimConfig(n_nodes=2, staging=True, window=15, stage_output_mb=48.0)
    )
    assert r.completed_ok
    moved = r.staged_bytes_avoided + r.cross_node_bytes
    assert moved <= cfg.host_budget_bytes * 2  # 2 nodes of budget


# --------------------------------------------------------------------------
# simulator control-plane cost model
# --------------------------------------------------------------------------


def test_sim_rpc_latency_charges_control_plane():
    from repro.core.simulator import SimConfig, run_simulation

    base = dict(n_nodes=2, staging=True, window=8, interconnect_gb_s=6.0)
    free = run_simulation(30, SimConfig(**base, rpc_latency_us=0.0))
    slow = run_simulation(30, SimConfig(**base, rpc_latency_us=50_000.0))
    assert free.completed_ok and slow.completed_ok
    assert free.control_messages == slow.control_messages > 0
    assert free.rpc_wait == 0.0
    assert slow.rpc_wait > 0.0
    assert slow.makespan > free.makespan


def _fanin_builder():
    """Three-stage fan-in: the sink stage pulls TWO upstream regions,
    so batch_prefetch has something to coalesce.  Op names come from
    the calibrated profiles (the simulator prices by name)."""
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


def test_sim_batched_prefetch_amortizes_rpc():
    from repro.core.simulator import SimConfig, run_simulation

    base = dict(
        n_nodes=3, staging=True, staging_locality=False, window=4,
        rpc_latency_us=20_000.0,
    )
    batched = run_simulation(
        30, SimConfig(**base, batch_prefetch=True),
        workflow_builder=_fanin_builder,
    )
    unbatched = run_simulation(
        30, SimConfig(**base, batch_prefetch=False),
        workflow_builder=_fanin_builder,
    )
    assert batched.completed_ok and unbatched.completed_ok
    # One message per batch vs one per key: fewer messages, less exposed
    # control-plane wait.  (Makespan is only loosely bounded — lease
    # ordering perturbations in the discrete-event model can outweigh a
    # few amortized round-trips.)
    assert batched.control_messages < unbatched.control_messages
    assert batched.rpc_wait < unbatched.rpc_wait
    assert batched.makespan <= unbatched.makespan * 1.05


# --------------------------------------------------------------------------
# real OS processes (slow tier)
# --------------------------------------------------------------------------


def _spawn_cluster(
    n_workers: int,
    n_chunks: int,
    mgr_cfg: ManagerConfig,
    registry: str = "repro.transport.demo:demo_registry",
):
    cw = demo_concrete(n_chunks)
    mgr = Manager(cw, mgr_cfg)
    endpoint = T.ManagerEndpoint(mgr, T.SocketBus())
    procs = [
        T.spawn_worker(
            endpoint.address,
            T.WorkerSpec(worker_id=wid, registry=registry),
        )
        for wid in range(n_workers)
    ]
    return cw, mgr, endpoint, procs


@pytest.mark.slow
def test_multiprocess_socketbus_run_matches_inproc():
    """Acceptance: Manager + 2 Workers in separate OS processes over
    SocketBus, staging + locality on, identical stage outputs."""
    cw, mgr, endpoint, procs = _spawn_cluster(
        2, N_CHUNKS,
        ManagerConfig(window=2, locality_aware=True, backup_tasks=False,
                      heartbeat_timeout=120.0),
    )
    try:
        assert endpoint.wait_workers(2, timeout=120.0)
        assert mgr.run(timeout=120.0)
        assert _consume_outputs(mgr, cw) == EXPECTED
        assert mgr.staged_bytes_avoided > 0  # locality actually engaged
    finally:
        endpoint.close()
        for p in procs:
            p.join(timeout=15.0)
    assert all(p.exitcode == 0 for p in procs)


@pytest.mark.slow
def test_multiprocess_worker_crash_heartbeat_reaped():
    """A killed worker process is reaped exactly like the inproc path:
    its leases are recovered and the run completes on the survivor."""
    cw, mgr, endpoint, procs = _spawn_cluster(
        2, N_CHUNKS,
        ManagerConfig(window=2, locality_aware=False, backup_tasks=False,
                      heartbeat_timeout=2.0, poll_interval=0.05),
        registry="repro.transport.demo:demo_slow_registry",
    )
    try:
        assert endpoint.wait_workers(2, timeout=120.0)
        done = threading.Event()
        run_ok = []

        def run():
            run_ok.append(mgr.run(timeout=120.0))
            done.set()

        threading.Thread(target=run, daemon=True).start()
        time.sleep(0.4)  # both workers hold leases mid-produce now
        procs[0].kill()  # SIGKILL: no goodbye message, just a dead peer
        assert done.wait(timeout=120.0)
        assert run_ok == [True]
        assert _consume_outputs(mgr, cw) == EXPECTED
        assert mgr.recovered_leases >= 1
    finally:
        endpoint.close()
        for p in procs:
            p.join(timeout=15.0)
