"""Online serving front end: admission control, weighted fair queuing,
deadline-aware (EDF) scheduling, and elastic membership under load."""

import itertools
import threading
import time

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
from repro.core.scheduling import HOST_KIND, ReadyScheduler
from repro.core.simulator import ClusterSim, SimConfig, segmentation_feature_workflow
from repro.core.workflow import Operation as Op, OperationInstance, StageInstance
from repro.serving import (
    GatewayConfig,
    RequestGateway,
    SHED,
    WorkloadConfig,
    generate_arrivals,
    zipf_weights,
)


# -- workload generator ------------------------------------------------------


def test_workload_generator_deterministic_and_sorted():
    cfg = WorkloadConfig(
        arrival_rate=200.0, duration_s=0.5,
        tenants={"a": 2.0, "b": 1.0}, deadline_ms=100.0, seed=42,
    )
    a1 = generate_arrivals(cfg)
    a2 = generate_arrivals(cfg)
    assert a1 == a2  # same seed, same trace
    assert a1 != generate_arrivals(
        WorkloadConfig(
            arrival_rate=200.0, duration_s=0.5,
            tenants={"a": 2.0, "b": 1.0}, deadline_ms=100.0, seed=43,
        )
    )
    assert all(x.t <= y.t for x, y in zip(a1, a1[1:]))  # merged by time
    assert {x.tenant for x in a1} == {"a", "b"}
    assert all(x.deadline_s == 0.1 for x in a1)
    # Open-loop Poisson: each tenant independently near its rate.
    for tenant in ("a", "b"):
        n = sum(1 for x in a1 if x.tenant == tenant)
        assert 50 <= n <= 160  # 100 expected, generous CI


def test_zipf_popularity_skews_to_head():
    w = zipf_weights(64, 1.1)
    assert abs(sum(w) - 1.0) < 1e-9
    assert all(x >= y for x, y in zip(w, w[1:]))  # monotone tail
    arr = generate_arrivals(
        WorkloadConfig(arrival_rate=2000.0, duration_s=1.0, n_tiles=64,
                       zipf_alpha=1.1, seed=7)
    )
    counts = {}
    for a in arr:
        counts[a.tile] = counts.get(a.tile, 0) + 1
    top = sum(counts.get(k, 0) for k in range(8))
    assert top / len(arr) > 0.4  # hot head dominates
    assert max(counts) < 64 and min(counts) >= 0


# -- EDF tier in the per-node scheduler --------------------------------------

_uid = itertools.count(50_000)


def _mk_task(speedup, deadline=None):
    si = StageInstance(uid=next(_uid), chunk=DataChunk(0), stage=None)
    oi = OperationInstance(
        uid=next(_uid), chunk=DataChunk(0), op=Op("op"), stage_instance=si,
    )
    oi.speedup = speedup
    oi.transfer_impact = 0.2
    oi.deps = set()
    oi.deadline = deadline
    return oi


def test_edf_tier_outranks_pats_order():
    s = ReadyScheduler("pats", deadline_aware=True)
    lax = _mk_task(50.0)                     # huge speedup, no deadline
    late = _mk_task(2.0, deadline=9.0)
    soon = _mk_task(1.0, deadline=1.0)
    for t in (lax, late, soon):
        s.push(t)
    # Deadline tasks drain first, earliest deadline first — even though
    # the no-deadline task has the best speedup.
    assert s.pop("gpu") is soon
    assert s.pop("gpu") is late
    assert s.pop("gpu") is lax
    assert s.pop("gpu") is None


def test_edf_group_respects_lane_affinity():
    s = ReadyScheduler("pats", deadline_aware=True)
    a = _mk_task(9.0, deadline=1.0)
    b = _mk_task(2.0, deadline=1.0)   # same deadline group
    c = _mk_task(5.0, deadline=4.0)
    for t in (a, b, c):
        s.push(t)
    # Within the earliest-deadline group, the accelerator still takes
    # the max speedup and the host the min (PATS inside EDF).
    assert s.pop("gpu") is a
    assert s.pop(HOST_KIND) is b
    assert s.pop(HOST_KIND) is c
    assert len(s) == 0


# -- threaded gateway: admission + completion --------------------------------


def _serving_registry(delay_s=0.002, stall_worker0=None):
    reg = VariantRegistry()

    def work(ctx):
        if stall_worker0 is not None and threading.current_thread().name.startswith(
            "worker0-"
        ):
            assert stall_worker0.wait(timeout=30.0)
        time.sleep(delay_s)
        return ctx.chunk.chunk_id

    reg.register("work", "cpu", work)
    return reg


def _serving_manager(reg, n_workers=1, **cfg_kwargs):
    wf = AbstractWorkflow.chain("serve", [Stage.single(Operation("work"))])
    cw = ConcreteWorkflow(wf)
    mgr = Manager(cw, ManagerConfig(window=4, backup_tasks=False, **cfg_kwargs))
    workers = []
    for wid in range(n_workers):
        rt = WorkerRuntime(wid, lanes=(LaneSpec("cpu", 0),), variant_registry=reg)
        rt.start()
        mgr.register_worker(rt)
        workers.append(rt)
    return mgr, workers


def test_gateway_admission_sheds_beyond_queue_cap():
    reg = _serving_registry(delay_s=0.01)
    mgr, workers = _serving_manager(reg)
    gw = RequestGateway(
        mgr, GatewayConfig(max_queue=4, max_inflight=1), tenants={"t": 1.0}
    )
    try:
        reqs = [gw.submit("t", DataChunk(i)) for i in range(30)]
        shed = [r for r in reqs if r.state == SHED]
        assert shed, "30 instant submissions must overflow a 4-deep queue"
        assert gw.stats.submitted == 30
        assert gw.stats.admitted + gw.stats.shed == 30
        assert gw.close(timeout=60.0)
        # Every admitted request completed; no shed request ever ran.
        assert gw.stats.completed == gw.stats.admitted
        assert all(r.t_dispatch is None for r in shed)
        assert all(r.latency is not None for r in reqs if r.accepted)
    finally:
        for rt in workers:
            rt.stop()


def test_gateway_estimated_work_cap():
    reg = _serving_registry(delay_s=0.001)
    mgr, workers = _serving_manager(reg)
    gw = RequestGateway(
        mgr,
        GatewayConfig(max_queue=10_000, max_est_work_s=0.5,
                      max_inflight=1, initial_cost_s=0.2),
        tenants={"t": 1.0},
    )
    try:
        reqs = [gw.submit("t", DataChunk(i)) for i in range(10)]
        # 0.2s estimate each against a 0.5s work budget: only a few fit.
        assert sum(1 for r in reqs if r.accepted) <= 4
        assert gw.stats.shed >= 6
        assert gw.close(timeout=60.0)
    finally:
        for rt in workers:
            rt.stop()


def test_gateway_wfq_dispatch_order_follows_weights():
    """With both tenants backlogged behind a blocked worker, releases
    go 3:1 by finish tags once the worker resumes."""
    gate = threading.Event()
    reg = _serving_registry(delay_s=0.0, stall_worker0=gate)
    mgr, workers = _serving_manager(reg)
    gw = RequestGateway(
        mgr, GatewayConfig(max_queue=64, max_inflight=1),
        tenants={"warm": 1.0, "a": 3.0, "b": 1.0},
    )
    try:
        gw.submit("warm", DataChunk(999))  # occupies the inflight slot
        a_reqs = [gw.submit("a", DataChunk(i)) for i in range(6)]
        b_reqs = [gw.submit("b", DataChunk(100 + i)) for i in range(2)]
        assert all(r.accepted for r in a_reqs + b_reqs)
        gate.set()
        assert gw.close(timeout=60.0)
        order = sorted(
            a_reqs + b_reqs, key=lambda r: r.t_dispatch
        )
        first8 = [r.tenant for r in order[:8]]
        # SFQ finish tags with weights 3:1 and unit cost: a at k/3,
        # b at k — the first eight releases are exactly 6 a's + 2 b's,
        # and three a's go before the first b.
        assert first8.count("a") == 6 and first8.count("b") == 2
        assert first8[:3] == ["a", "a", "a"]
    finally:
        gate.set()
        for rt in workers:
            rt.stop()


def test_gateway_elastic_drain_and_join_zero_lost_requests():
    """Drain a worker holding leases mid-stream, join a fresh one later:
    every admitted request still completes (the drain re-queues leases
    and releases push reservations atomically)."""
    stall0 = threading.Event()  # worker 0 wedges until drained
    reg = _serving_registry(delay_s=0.002, stall_worker0=stall0)
    mgr, workers = _serving_manager(reg, n_workers=2, heartbeat_timeout=60.0)
    gw = RequestGateway(
        mgr, GatewayConfig(max_queue=256, max_inflight=8),
        tenants={"t": 1.0},
    )
    try:
        reqs = [gw.submit("t", DataChunk(i)) for i in range(16)]
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with mgr._lock:
                if mgr._workers[0].leases:
                    break
            time.sleep(0.005)
        # Seed a push reservation toward the draining worker: drain
        # must release it (regression: it used to leak, wedging the
        # ingress cap on a corpse).
        from repro.core.manager import _PushInFlight

        with mgr._lock:
            mgr._push_inbound[(0, "region-x")] = _PushInFlight(
                time.monotonic(), 1 << 20
            )
            mgr._push_inflight_bytes[0] = 1 << 20
        requeued = mgr.drain_worker(0)
        assert requeued >= 1  # it really held leases
        with mgr._lock:
            assert 0 not in mgr._push_inflight_bytes
            assert 0 not in mgr._push_deferred
        reqs += [gw.submit("t", DataChunk(100 + i)) for i in range(8)]
        w2 = WorkerRuntime(2, lanes=(LaneSpec("cpu", 0),), variant_registry=reg)
        w2.start()
        workers.append(w2)
        mgr.register_worker(w2)
        assert gw.close(timeout=60.0)
        assert gw.stats.completed == gw.stats.admitted == len(reqs)
        assert all(r.state == "done" for r in reqs)
        assert mgr.recovered_leases >= requeued
    finally:
        stall0.set()
        for rt in workers:
            rt.stop()


def test_streaming_manager_is_reusable_between_requests():
    """The stream stays open across quiet periods: progress-done must
    not fire while streaming, and close() drains cleanly."""
    reg = _serving_registry(delay_s=0.001)
    mgr, workers = _serving_manager(reg)
    gw = RequestGateway(mgr, GatewayConfig(max_queue=8), tenants={"t": 1.0})
    try:
        r1 = gw.submit("t", DataChunk(0))
        assert r1.wait(timeout=30.0)
        # Idle gap: the manager must not declare the run finished.
        assert mgr._monitor is not None and mgr._monitor.is_alive()
        r2 = gw.submit("t", DataChunk(1))
        assert r2.wait(timeout=30.0)
        assert gw.close(timeout=30.0)
        assert gw.stats.completed == 2
    finally:
        for rt in workers:
            rt.stop()


# -- serving over the transport bus ------------------------------------------


def test_serving_client_submit_and_status_over_inproc_bus():
    import repro.transport as T

    reg = _serving_registry(delay_s=0.001)
    mgr, workers = _serving_manager(reg, n_workers=0)
    endpoint = T.ManagerEndpoint(mgr, T.InprocBus())
    rt = WorkerRuntime(0, lanes=(LaneSpec("cpu", 0),), variant_registry=reg)
    rt.start()
    workers.append(rt)
    T.WorkerClient(rt, T.InprocBus(), endpoint.address)
    assert endpoint.wait_workers(1, timeout=30.0)
    gw = RequestGateway(mgr, GatewayConfig(max_queue=64), tenants={"t": 1.0})
    endpoint.attach_gateway(gw)
    client = T.ServingClient(T.InprocBus(), endpoint.address)
    try:
        acks = [client.submit(i, tenant="t", deadline_ms=5000.0) for i in range(4)]
        assert all(a["ok"] and a["accepted"] for a in acks)
        assert gw.drain(timeout=30.0)
        for a in acks:
            st = client.status(a["req_id"])
            assert st["ok"] and st["state"] == "done" and st["tenant"] == "t"
            assert st["latency"] > 0.0
        assert gw.stats.completed == 4
    finally:
        client.close()
        for w in workers:
            w.stop()
        endpoint.bus.close()


# -- simulator serving mode --------------------------------------------------


def _serve_sim(**kwargs):
    cfg = SimConfig(**kwargs)
    cw = ConcreteWorkflow(segmentation_feature_workflow(cfg.fused_features))
    return cfg, ClusterSim(cw, cfg)


def test_sim_serving_completes_and_reports_percentiles():
    cfg, sim = _serve_sim(
        n_nodes=2, arrival_rate=5.0, serve_duration_s=0.5,
        tenants={"t0": 1.0}, deadline_ms=5000.0,
        admission_queue_cap=64, seed=1,
    )
    r = sim.run()
    assert r.requests > 0
    assert r.completed_requests + r.shed_requests == r.requests
    assert r.completed_ok
    assert r.latency_p99 >= r.latency_p50 > 0.0


def test_sim_admission_bounds_admitted_p99_at_overload():
    """Offered 1.5x the measured capacity, a queue-depth cap keeps the
    admitted p99 within 3x the half-load p99 by shedding, while the
    uncontrolled queue admits everything and its tail grows past it."""
    base = dict(n_nodes=2, serve_duration_s=30.0, tenants={"t0": 1.0},
                gateway_inflight=16, seed=17)

    def run(rate, cap):
        return _serve_sim(**base, arrival_rate=rate,
                          admission_queue_cap=cap)[1].run()

    sat = run(50.0, 10_000)
    mu = sat.completed_requests / sat.makespan
    half, on, off = run(0.5 * mu, 16), run(1.5 * mu, 16), run(1.5 * mu, None)
    assert half.completed_ok and on.completed_ok and off.completed_ok
    assert on.shed_requests > 0 and off.shed_requests == 0
    assert on.latency_p99 <= 3.0 * half.latency_p99
    assert off.latency_p99 > on.latency_p99


def test_sim_two_tenant_fairness_tracks_weights():
    """Sustained 2:1 overload: completions inside the arrival window
    split by the configured weights within 10%."""
    cfg, sim = _serve_sim(
        n_nodes=8, arrival_rate=30.0, serve_duration_s=60.0,
        tenants={"a": 2.0, "b": 1.0},
        admission_queue_cap=64, gateway_inflight=16, seed=3,
    )
    r = sim.run(max_time=60.0)
    a = r.tenant_completed.get("a", 0)
    b = r.tenant_completed.get("b", 0)
    assert a + b >= 80  # enough completions to measure
    share = a / (a + b)
    assert abs(share - 2.0 / 3.0) <= 0.1 * (2.0 / 3.0), (a, b)


def test_sim_edf_beats_fifo_on_tail_tardiness():
    """Mixed deadline classes at moderate load: stamping deadlines into
    the schedulers (EDF tier) cuts p99 tardiness vs the FIFO baseline
    that measures but never prioritizes."""

    def run(edf, seed):
        cfg, sim = _serve_sim(
            n_nodes=4, arrival_rate=0.5, serve_duration_s=60.0,
            tenants={"urgent": 1.0, "lax": 1.0},
            deadline_ms={"urgent": 2500.0, "lax": 60000.0},
            admission_queue_cap=256, gateway_inflight=32,
            edf=edf, seed=seed,
        )
        return sim.run()

    edf_tard = fifo_tard = 0.0
    for seed in (7, 11, 13):
        r_edf, r_fifo = run(True, seed), run(False, seed)
        assert r_edf.completed_requests == r_fifo.completed_requests
        edf_tard += r_edf.tardiness_p99
        fifo_tard += r_fifo.tardiness_p99
    assert edf_tard < fifo_tard, (edf_tard, fifo_tard)


def test_sim_elastic_drain_and_join_zero_lost():
    """Drain one node mid-stream and join a fresh one later: every
    admitted request completes (drain re-queues leases immediately)."""
    cfg, sim = _serve_sim(
        n_nodes=3, arrival_rate=2.0, serve_duration_s=4.0,
        tenants={"t0": 1.0}, admission_queue_cap=256,
        drain_node_at=(0, 1.0), join_node_at=2.0, seed=9,
    )
    r = sim.run()
    assert r.completed_ok
    assert r.completed_requests + r.shed_requests == r.requests
    assert r.recovered_leases >= 0
    assert not sim.nodes[0].alive       # drained stayed out
    assert sim.nodes[cfg.n_nodes].alive  # joiner came in
