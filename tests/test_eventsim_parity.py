"""Differential tick-vs-event parity suite.

PR 10 rewrote the simulator's transfer engine: store-and-forward link
reservation (each copy holds whole links back-to-back) became fluid
flows on first-class NetworkLink objects with max-min fair progressive
filling, completion driven by transfer_progress events.  Nine PRs'
worth of simulator-backed claims lean on the old engine's numbers, so
the new core must reproduce them: this suite runs both engines across
a pinned seed x config matrix (baseline staging, fat-tree 8:1,
predictive push, coordinator relay, 1% faults, straggler, serving
mode) and asserts makespan / throughput / relay-bytes / miss-rate
agree within 5%.

The two models are *different physics* under heavy contention (that is
the point of the rewrite — store-and-forward exaggerates uplink
serialization), so the matrix pins moderate-contention cells where an
honest engine must agree with the legacy one; the contention delta
itself is discussed in docs/simulator.md.
"""

from __future__ import annotations

import pytest

from repro.core.simulator import SimConfig, SimResult, run_simulation
from repro.core.workflow import AbstractWorkflow, Operation, Stage

SEEDS = (3, 11)
TOL = 0.05          # relative tolerance on makespan / throughput / bytes
MISS_TOL = 0.05     # absolute tolerance on deadline-miss rate


def _diamond_builder() -> AbstractWorkflow:
    """Fan-out + fan-in: one producer feeding four feature stages that
    merge into an aggregate.  The fan-out leaves dependents pending on
    other nodes (cross-node pulls are guaranteed) and the fan-in gives
    predictive push its trigger (push toward the node running a sibling
    upstream) — without both, the engines share every code path and the
    diff would be vacuous."""
    feats = ("pixel_stats", "gradient_stats", "haralick", "canny_edge")
    stages = (
        [Stage.single(Operation("recon_to_nuclei"))]
        + [Stage.single(Operation(f)) for f in feats]
        + [Stage.single(Operation("morphometry"))]
    )
    edges = tuple(("recon_to_nuclei", f) for f in feats) + tuple(
        (f, "morphometry") for f in feats
    )
    return AbstractWorkflow("diamond", tuple(stages), edges)


_STAGE = dict(
    n_nodes=8,
    staging=True,
    staging_locality=True,
    window=1,
    stage_output_mb=64.0,
    interconnect_gb_s=1.0,
)

# The pinned config matrix (ISSUE 10 satellite 1).
MATRIX: dict[str, dict] = {
    "baseline": dict(_STAGE),
    "fat_tree_8to1": dict(
        _STAGE,
        stage_output_mb=32.0,
        network="fat_tree",
        rack_size=2,
        oversubscription=8.0,
        rack_affinity=0.5,
    ),
    "predictive_push": dict(_STAGE, predictive_push=True),
    "relay": dict(_STAGE, stage_output_mb=96.0, direct_transfer=False),
    "faults_1pct": dict(
        _STAGE, msg_drop_rate=0.01, corrupt_rate=0.02, rpc_latency_us=200.0
    ),
    "straggler": dict(_STAGE, straggler_factor={1: 4.0}),
    "serving": dict(
        _STAGE,
        stage_output_mb=8.0,
        arrival_rate=12.0,
        serve_duration_s=4.0,
        tenants={"a": 2.0, "b": 1.0},
        deadline_ms=6000.0,
        gateway_inflight=8,
        admission_queue_cap=64,
    ),
}


def _run(name: str, engine: str, seed: int) -> SimResult:
    cfg = SimConfig(engine=engine, seed=seed, **MATRIX[name])
    n = 0 if cfg.arrival_rate is not None else 96
    return run_simulation(n, cfg, workflow_builder=_diamond_builder)


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(a), abs(b), 1e-12)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(MATRIX))
def test_event_core_matches_tick_core(name: str, seed: int) -> None:
    tick = _run(name, "tick", seed)
    event = _run(name, "event", seed)
    assert tick.completed_ok and event.completed_ok
    assert _rel(tick.makespan, event.makespan) <= TOL, (
        f"makespan diverged: tick={tick.makespan} event={event.makespan}"
    )
    assert _rel(tick.tiles_per_second, event.tiles_per_second) <= TOL
    assert _rel(tick.relay_region_bytes, event.relay_region_bytes) <= TOL
    assert abs(tick.miss_rate - event.miss_rate) <= MISS_TOL
    if MATRIX[name].get("arrival_rate") is not None:
        # Same requests arrive (shared workload generator) and both
        # engines drain them all.
        assert tick.requests == event.requests
        assert tick.completed_requests + tick.shed_requests == tick.requests
        assert event.completed_requests + event.shed_requests == event.requests


def test_relay_cell_actually_relays() -> None:
    """Guard against a vacuous relay-bytes comparison: the relay cell
    must move coordinator-relayed bytes on both engines."""
    tick = _run("relay", "tick", SEEDS[0])
    event = _run("relay", "event", SEEDS[0])
    assert tick.relay_region_bytes > 0
    assert event.relay_region_bytes > 0
    assert tick.direct_region_bytes == 0
    assert event.direct_region_bytes == 0


def test_push_cell_actually_pushes() -> None:
    """The diamond's fan-in is what arms predictive push (the region is
    pushed toward the node running a sibling upstream); both engines
    must actually take that path in the push cell."""
    tick = _run("predictive_push", "tick", SEEDS[0])
    event = _run("predictive_push", "event", SEEDS[0])
    assert tick.pushes > 0
    assert event.pushes > 0
    assert event.pushed_bytes > 0


def test_matrix_cells_actually_transfer() -> None:
    """Every matrix cell must exercise the engine under test: no
    cross-node traffic means the tick and event paths never diverge
    and the parity assertion proves nothing."""
    for name in MATRIX:
        r = _run(name, "event", SEEDS[0])
        assert r.cross_node_bytes > 0, f"cell {name!r} moved no bytes"


def _counts(engine: str) -> dict:
    # SimResult doesn't carry per-kind event counts; run via a sim
    # handle for the assertions that need them.
    from repro.core.simulator import ClusterSim, ConcreteWorkflow, make_tiles

    cfg = SimConfig(engine=engine, seed=SEEDS[0], **MATRIX["baseline"])
    cw = ConcreteWorkflow.replicate(
        _diamond_builder(), make_tiles(96, seed=cfg.seed)
    )
    sim = ClusterSim(cw, cfg)
    sim.run()
    return sim.event_counts


def test_engines_emit_expected_event_kinds() -> None:
    """The tick engine serializes copies inline at future-time gates;
    only the event engine drives transfers through the queue as
    transfer_progress events.  Both lease and complete ops."""
    tick, event = _counts("tick"), _counts("event")
    for counts in (tick, event):
        assert counts.get("lease", 0) > 0
        assert counts.get("op_done", 0) > 0
    assert event.get("transfer_progress", 0) > 0


def test_engine_knob_validated() -> None:
    with pytest.raises(ValueError):
        SimConfig(engine="warp")
    with pytest.raises(ValueError):
        SimConfig(rack_affinity="australia")
