"""Device-resident chaining, the scheduler's and simulator's
micro-batched dispatch, and the PATS online-EMA path (hypothesis-free:
always collected)."""

import itertools
import threading
import time

import numpy as np
import pytest

from repro.core import (
    AbstractWorkflow,
    ConcreteWorkflow,
    DataChunk,
    LaneSpec,
    Operation,
    Stage,
    VariantRegistry,
    WorkerRuntime,
)
from repro.core.scheduling import HOST_KIND, ReadyScheduler
from repro.core.simulator import SimConfig, run_simulation
from repro.core.workflow import OperationInstance, StageInstance
from repro.staging import HostTier, PlacementDirectory, op_key

_uid = itertools.count(50_000)


def mk_task(speedup, deps=(), ti=0.2, name="op"):
    si = StageInstance(uid=next(_uid), chunk=DataChunk(0), stage=None)
    oi = OperationInstance(
        uid=next(_uid), chunk=DataChunk(0), op=Operation(name),
        stage_instance=si,
    )
    oi.speedup = speedup
    oi.transfer_impact = ti
    oi.deps = set(deps)
    return oi


# -- scheduler: chain affinity ------------------------------------------------


def test_chain_affinity_bonus_flips_dl_decision():
    """S_d=5, S_q=9, ti=0.2: plain DL picks the queued op (5 < 7.2);
    with chain affinity the dependent's own transfer fraction is
    recovered (5/0.75 ≈ 6.67)... still loses; at ti_d=0.35 it wins."""
    plain = ReadyScheduler("pats", locality=True)
    dep = mk_task(6.0, deps=[1], ti=0.35)
    q = mk_task(9.0, ti=0.2)
    plain.push(dep)
    plain.push(q)
    assert plain.pop("gpu", resident_producers={1}) is q  # 6 < 7.2

    chained = ReadyScheduler("pats", locality=True, chain_affinity=1.0)
    dep2 = mk_task(6.0, deps=[1], ti=0.35)
    q2 = mk_task(9.0, ti=0.2)
    chained.push(dep2)
    chained.push(q2)
    # 6 / (1 - 0.35) ≈ 9.23 >= 7.2: the chained dependent now wins.
    assert chained.pop("gpu", resident_producers={1}) is dep2
    assert chained.stats.reuse_hits == 1


# -- scheduler: micro-batched pop --------------------------------------------


def test_pop_batch_collects_same_op_instances():
    s = ReadyScheduler("pats")
    a1, a2, a3 = (mk_task(x, name="a") for x in (10.0, 8.0, 6.0))
    b1 = mk_task(9.0, name="b")
    for t in (a1, b1, a2, a3):
        s.push(t)
    batch = s.pop_batch("gpu", limit=8, batchable=lambda t: 8)
    assert batch[0] is a1  # head still chosen by PATS (max speedup)
    assert {t.uid for t in batch} == {a1.uid, a2.uid, a3.uid}
    assert s.stats.batches == 1 and s.stats.batched_ops == 3
    # The different op stays queued and pops normally.
    assert s.pop("gpu") is b1
    assert len(s) == 0


def test_pop_batch_respects_batch_cap_and_fcfs():
    s = ReadyScheduler("fcfs")
    tasks = [mk_task(1.0, name="x") for _ in range(4)]
    for t in tasks:
        s.push(t)
    # Cap 1 = scalar dispatch even when limit allows more.
    assert s.pop_batch("gpu", limit=4, batchable=lambda t: 1) == [tasks[0]]
    assert s.stats.batches == 0
    # The head op's own cap bounds the batch below the lane limit: a
    # batched implementation never sees more contexts than max_batch.
    batch = s.pop_batch("gpu", limit=4, batchable=lambda t: 2)
    assert batch == [tasks[1], tasks[2]]
    assert s.pop_batch("gpu", limit=4, batchable=lambda t: 8) == [tasks[3]]


# -- scheduler: online-EMA reorder (satellite) --------------------------------


def _observe(var, kind, seconds, n=3):
    for _ in range(n):
        var.observe_runtime(kind, seconds)


def test_observed_runtime_updates_reorder_ready_queue():
    """PATS pops by estimated speedup; once the online EMA inverts two
    ops' order, reestimate() must re-sort already-queued instances."""
    reg = VariantRegistry()
    reg.register("fast", "cpu", lambda ctx: None)
    reg.register("fast", "gpu", lambda ctx: None, speedup=20.0)
    reg.register("slow", "cpu", lambda ctx: None)
    reg.register("slow", "gpu", lambda ctx: None, speedup=2.0)

    s = ReadyScheduler("pats")
    t_fast = mk_task(reg.get("fast").estimate_speedup("gpu"), name="fast")
    t_slow = mk_task(reg.get("slow").estimate_speedup("gpu"), name="slow")
    t_fast.op = Operation("fast")
    t_slow.op = Operation("slow")
    s.push(t_fast)
    s.push(t_slow)

    # Observations invert the static estimates: "slow" measures 50x,
    # "fast" measures 1.25x.
    _observe(reg.get("fast"), "cpu", 1.0)
    _observe(reg.get("fast"), "gpu", 0.8)
    _observe(reg.get("slow"), "cpu", 1.0)
    _observe(reg.get("slow"), "gpu", 0.02)
    assert reg.get("slow").estimate_speedup("gpu") > reg.get(
        "fast"
    ).estimate_speedup("gpu")

    s.reestimate(lambda t: reg.get(t.op.name).estimate_speedup("gpu"))
    # The accelerator now takes the op the EMA proved fastest.
    assert s.pop("gpu") is t_slow
    assert s.pop(HOST_KIND) is t_fast


# -- worker runtime: chaining -------------------------------------------------


def _chain_setup(reg, n_ops=4, n_chunks=8):
    def step(ctx):
        if not ctx.inputs:
            return np.full((32, 32), float(ctx.chunk.chunk_id), np.float32)
        return next(iter(ctx.inputs.values())) + 1.0

    names = [f"s{i}" for i in range(n_ops)]
    for name in names:
        reg.register(name, "cpu", step)
        reg.register(name, "gpu", step, speedup=8.0, transfer_impact=0.2)
    wf = AbstractWorkflow.chain(
        "chain", [Stage.chain("chain", [Operation(n) for n in names])]
    )
    return ConcreteWorkflow.replicate(
        wf, [DataChunk(i) for i in range(n_chunks)]
    )


def test_chained_execution_correct_and_records_reuse_hits():
    """Satellite: chained assignments must record reuse_hits, and the
    resident fast path must not change results."""
    reg = VariantRegistry()
    cw = _chain_setup(reg, n_ops=4, n_chunks=8)
    rt = WorkerRuntime(
        0, lanes=(LaneSpec("gpu", 0),), policy="pats", chaining=True,
        variant_registry=reg,
    )
    rt.start()
    try:
        for si in cw.stage_instances.values():
            rt.submit_stage(si)
        assert rt.drain(timeout=60.0)
        assert not rt.errors
        for si in cw.stage_instances.values():
            last = [o for o in si.op_instances if o.op.name == "s3"][0]
            out = rt.output_of(last.uid)
            assert float(np.asarray(out)[0, 0]) == si.chunk.chunk_id + 3.0
        stats = rt.stats()
        assert rt.scheduler.stats.reuse_hits > 0
        assert stats["chain_hits"] > 0
        assert stats["chain_deferred"] > 0
    finally:
        rt.stop()


def test_chained_outputs_survive_device_eviction():
    """Tiny device memory: LRU spills must write device-only chained
    outputs back to the host tier, never lose them."""
    reg = VariantRegistry()
    cw = _chain_setup(reg, n_ops=6, n_chunks=12)
    rt = WorkerRuntime(
        0, lanes=(LaneSpec("gpu", 0, memory_slots=3),), policy="fcfs",
        chaining=True, variant_registry=reg,
    )
    rt.start()
    try:
        for si in cw.stage_instances.values():
            rt.submit_stage(si)
        assert rt.drain(timeout=60.0)
        assert not rt.errors
        for si in cw.stage_instances.values():
            last = [o for o in si.op_instances if o.op.name == "s5"][0]
            out = rt.output_of(last.uid)
            assert float(np.asarray(out)[0, 0]) == si.chunk.chunk_id + 5.0
        assert rt.stats()["chain_writebacks"] > 0
    finally:
        rt.stop()


def test_chaining_skips_host_materialization():
    """A fully-chained 1-lane run defers every intermediate: the host
    tier sees only what stage completion / eviction actually needs."""
    reg = VariantRegistry()
    cw = _chain_setup(reg, n_ops=4, n_chunks=4)
    rt = WorkerRuntime(
        0, lanes=(LaneSpec("gpu", 0),), policy="pats", chaining=True,
        variant_registry=reg,
    )
    rt.start()
    try:
        for si in cw.stage_instances.values():
            rt.submit_stage(si)
        assert rt.drain(timeout=60.0)
        stats = rt.stats()
        # 3 of 4 ops per chunk have local dependents => deferred.
        assert stats["chain_deferred"] == 3 * 4
        assert stats["chain_hits"] == 3 * 4
        # The only downloads are lazy materializations (here: none —
        # the sink op is never deferred, intermediates die on device).
        assert stats["downloads"] == stats["chain_writebacks"]
    finally:
        rt.stop()


def test_resubmitted_stage_does_not_duplicate_ops():
    """A heartbeat-slander rejoin re-leases recovered stages to the
    worker that still holds them; submit_stage must be idempotent or
    lanes execute duplicate op instances."""
    reg = VariantRegistry()

    def work(ctx):
        time.sleep(0.02)
        return ctx.chunk.chunk_id

    reg.register("work", "cpu", work)
    wf = AbstractWorkflow.chain(
        "resubmit", [Stage.chain("s", [Operation("work"), Operation("work2")])]
    )
    reg.register("work2", "cpu", work)
    cw = ConcreteWorkflow.replicate(wf, [DataChunk(i) for i in range(4)])
    rt = WorkerRuntime(0, lanes=(LaneSpec("cpu", 0),), variant_registry=reg)
    rt.start()
    try:
        for si in cw.stage_instances.values():
            rt.submit_stage(si)
            rt.submit_stage(si)  # re-lease of a still-held stage
        assert rt.drain(timeout=60.0)
        assert len(rt.completion_order) == len(set(rt.completion_order)) == 8
        assert rt.stats()["executed"] == 8
    finally:
        rt.stop()


def test_micro_batch_isolates_single_op_failure():
    """One malformed chunk must not poison the lane: its stage alone is
    reported failed, once, and the same lane runs every other chunk's op."""
    reg = VariantRegistry()
    lanes_run: dict[int, str] = {}

    def flaky(ctx):
        lanes_run[ctx.chunk.chunk_id] = threading.current_thread().name
        if ctx.chunk.chunk_id == 3:
            raise ValueError("malformed tile")
        return ctx.chunk.chunk_id * 2.0

    reg.register("flaky", "cpu", flaky)
    reg.register("flaky", "gpu", flaky, speedup=5.0)
    wf = AbstractWorkflow.chain("iso", [Stage.single(Operation("flaky"))])
    cw = ConcreteWorkflow.replicate(wf, [DataChunk(i) for i in range(8)])
    rt = WorkerRuntime(0, lanes=(LaneSpec("gpu", 0),), policy="fcfs",
                       variant_registry=reg)
    failed: list[tuple[int, str]] = []
    rt.on_stage_failed = lambda si, why: failed.append((si.chunk.chunk_id, why))
    rt.start()
    try:
        for si in cw.stage_instances.values():
            rt.submit_stage(si)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and (
            len(rt.completion_order) < 7 or not failed
        ):
            time.sleep(0.01)
        assert failed == [(3, "ValueError: malformed tile")]
        assert len(rt.completion_order) == 7  # all but the bad chunk
        assert len(rt.errors) == 1
        uid, exc = rt.errors[0]
        assert isinstance(exc, ValueError)
        assert cw.op_instances[uid].chunk.chunk_id == 3
        for si in cw.stage_instances.values():
            if si.chunk.chunk_id != 3:
                assert rt.output_of(si.op_instances[0].uid) == (
                    si.chunk.chunk_id * 2.0
                )
        assert set(lanes_run) == set(range(8))
        assert set(lanes_run.values()) == {"worker0-gpu0"}
    finally:
        rt.stop()


# -- replication-aware eviction (satellite) -----------------------------------


def test_host_tier_evicts_replicated_regions_first():
    replicated = {op_key(0), op_key(1)}
    t = HostTier(budget_bytes=4 * 1024)
    t.replicated = lambda k: k in replicated
    for i in range(4):
        t.put(op_key(i), np.zeros(1024, dtype=np.uint8))
    # Adding a 5th region must evict a *replicated* one, not the LRU
    # sole copy op2.
    t.put(op_key(9), np.zeros(1024, dtype=np.uint8))
    assert op_key(0) not in t          # replicated LRU went first
    assert op_key(2) in t and op_key(3) in t
    assert t.replicated_evictions == 1
    # Without replicated candidates, plain LRU among sole copies.
    t.put(op_key(10), np.zeros(2 * 1024, dtype=np.uint8))
    assert t.used_bytes <= 4 * 1024


def test_store_drop_hook_keeps_directory_honest():
    """A region falling off the bottom tier must leave the directory,
    or replicated_elsewhere would point at replicas that are gone."""
    from repro.staging import RegionStore

    d = PlacementDirectory()
    store = RegionStore([HostTier(budget_bytes=2 * 1024)])
    store.on_drop = lambda key: d.evict(0, key)
    a = np.zeros(1024, dtype=np.uint8)
    for i in range(4):
        store.put(op_key(i), a.copy())
        d.record(0, op_key(i), a.nbytes)
    # Budget 2KB: the two oldest fell off the (bottom) host tier.
    assert store.dropped == 2
    assert d.holders(op_key(0)) == {} and d.holders(op_key(1)) == {}
    assert d.holders(op_key(3)) == {0: a.nbytes}


def test_directory_replicated_elsewhere():
    d = PlacementDirectory()
    d.record(0, op_key(1), 100)
    assert not d.replicated_elsewhere(0, op_key(1))  # sole copy
    d.record(1, op_key(1), 100)
    assert d.replicated_elsewhere(0, op_key(1))
    d.evict(1, op_key(1))
    assert not d.replicated_elsewhere(0, op_key(1))
    assert not d.replicated_elsewhere(0, op_key(42))  # unknown key


# -- simulator: batching + chaining knobs -------------------------------------


def test_simulator_micro_batching_amortizes_launch_overhead():
    base = dict(policy="pats", window=64, launch_overhead=0.1)
    off = run_simulation(80, SimConfig(**base))
    on = run_simulation(80, SimConfig(**base, micro_batch=8))
    assert on.completed_ok and off.completed_ok
    assert on.batched_ops > 0 and on.batches > 0
    assert on.makespan < off.makespan  # fewer launches, same work
    zero = run_simulation(80, SimConfig(policy="pats", window=64))
    assert zero.batches == 0  # micro_batch=1: no batched pops


def test_simulator_chaining_implies_locality_and_completes():
    r = run_simulation(60, SimConfig(policy="pats", window=16, chaining=True))
    assert r.completed_ok
    assert r.reuse_hits > 0


def test_simulator_fused_feature_workflow_completes_faster():
    base = dict(policy="pats", window=24, chaining=True, prefetch=True,
                launch_overhead=0.05)
    plain = run_simulation(60, SimConfig(**base))
    fused = run_simulation(60, SimConfig(**base, fused_features=True))
    assert fused.completed_ok
    assert "feature_fused" in fused.profile
    # Fewer ops + lower transfer: fused never slower than split.
    assert fused.makespan <= plain.makespan * 1.02


@pytest.mark.slow
def test_simulator_batch_size_sweep_monotone():
    """Sweep the batched-runtime tradeoff: larger batches amortize more
    launch overhead (work-conserving limit prevents latency cliffs)."""
    base = dict(policy="pats", window=128, launch_overhead=0.12)
    spans = [
        run_simulation(200, SimConfig(**base, micro_batch=b)).makespan
        for b in (1, 2, 4, 8, 16)
    ]
    assert spans[-1] < spans[0] * 0.85
    for a, b in zip(spans, spans[1:]):
        assert b < a * 1.05  # never materially worse


# -- worker runtime: chained CPU lanes ----------------------------------------


def test_host_lane_chaining_skips_region_store_roundtrip():
    """Satellite (ROADMAP): host lanes get the same dependent-affinity
    as accelerator lanes — a CPU-resident chain's intermediates never
    round-trip through the region store."""
    reg = VariantRegistry()
    cw = _chain_setup(reg, n_ops=4, n_chunks=6)
    rt = WorkerRuntime(
        0, lanes=(LaneSpec("cpu", 0),), policy="fcfs", chaining=True,
        variant_registry=reg,
    )
    rt.start()
    try:
        for si in cw.stage_instances.values():
            rt.submit_stage(si)
        assert rt.drain(timeout=60.0)
        assert not rt.errors
        for si in cw.stage_instances.values():
            last = [o for o in si.op_instances if o.op.name == "s3"][0]
            out = rt.output_of(last.uid)
            assert float(np.asarray(out)[0, 0]) == si.chunk.chunk_id + 3.0
        stats = rt.stats()
        # 3 of 4 ops per chunk have local dependents => deferred, and
        # every dependent read was served from the chain dict.
        assert stats["host_chain_deferred"] == 3 * 6
        assert stats["host_chain_hits"] == 3 * 6
        # The store only ever saw the sink outputs: no intermediate put.
        host_puts = rt.store.tier("host").stats.puts
        assert host_puts == 6  # one sink per chunk
    finally:
        rt.stop()


def test_host_lane_chaining_matches_unchained_results():
    """Chained and unchained host-lane runs produce identical sinks."""
    outs = {}
    for chaining in (False, True):
        reg = VariantRegistry()
        cw = _chain_setup(reg, n_ops=5, n_chunks=5)
        rt = WorkerRuntime(
            0, lanes=(LaneSpec("cpu", 0),), policy="fcfs",
            chaining=chaining, variant_registry=reg,
        )
        rt.start()
        try:
            for si in cw.stage_instances.values():
                rt.submit_stage(si)
            assert rt.drain(timeout=60.0)
            assert not rt.errors
            outs[chaining] = sorted(
                float(np.asarray(rt.output_of(o.uid))[0, 0])
                for si in cw.stage_instances.values()
                for o in si.op_instances
                if o.op.name == "s4"
            )
        finally:
            rt.stop()
    assert outs[True] == outs[False]


def test_host_chained_sink_materializes_for_remote_pull():
    """A host-chained stage sink (its consumer stage is already leased
    here) must materialize to the host tier at stage completion so a
    Manager pull (pull_region) can serve it to another worker."""
    from repro.staging import op_key as _ok

    reg = VariantRegistry()

    def step(ctx):
        if not ctx.inputs:
            return np.full((16, 16), float(ctx.chunk.chunk_id), np.float32)
        return next(iter(ctx.inputs.values())) + 1.0

    for name in ("a0", "a1", "b0"):
        reg.register(name, "cpu", step)
    wf = AbstractWorkflow.chain(
        "two-stage",
        [
            Stage.chain("A", [Operation("a0"), Operation("a1")]),
            Stage.single(Operation("b0")),
        ],
    )
    cw = ConcreteWorkflow.replicate(wf, [DataChunk(i) for i in range(3)])
    done = []
    rt = WorkerRuntime(
        0, lanes=(LaneSpec("cpu", 0),), policy="fcfs", chaining=True,
        variant_registry=reg,
        on_stage_complete=lambda si, outputs, exec_s=None: done.append(
            (si, outputs)
        ),
    )
    rt.start()
    try:
        # Both stages of every chunk are leased up-front, so stage A's
        # sink a1 sees its consumer locally and chains.
        for si in cw.stage_instances.values():
            rt.submit_stage(si)
        assert rt.drain(timeout=60.0)
        assert not rt.errors
        stats = rt.stats()
        assert stats["host_chain_deferred"] >= 3  # a0 chains; a1 too
        assert stats["host_chain_writebacks"] >= 3  # a1 materialized
        for si, outputs in done:
            if si.stage.name != "A":
                continue
            sink = [o for o in si.op_instances if o.op.name == "a1"][0]
            pulled = rt.pull_region(_ok(sink.uid))
            assert float(np.asarray(pulled)[0, 0]) == si.chunk.chunk_id + 1.0
    finally:
        rt.stop()
