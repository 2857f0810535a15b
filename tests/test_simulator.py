"""Cluster-simulator validation against the paper's claims (bands)."""

import pytest

from repro.core.simulator import SimConfig, run_simulation

TILES = 60  # reduced tile count keeps test time low; bands are wide


def run(policy="pats", window=16, **kw):
    return run_simulation(TILES, SimConfig(policy=policy, window=window, **kw))


def test_everything_completes():
    r = run()
    assert r.completed_ok
    assert r.tiles == TILES


def test_pats_equals_fcfs_at_window_12():
    # Table II: with 12 lanes and window 12 the decision is trivial.
    f = run(policy="fcfs", window=12)
    p = run(policy="pats", window=12)
    assert abs(p.makespan - f.makespan) / f.makespan < 0.05


def test_pats_beats_fcfs_with_window():
    f = run(policy="fcfs", window=16)
    p = run(policy="pats", window=16)
    assert p.makespan < f.makespan * 0.85  # paper: ~1.33-1.48x


def test_fcfs_flat_in_window():
    t = [run(policy="fcfs", window=w).makespan for w in (12, 15, 19)]
    assert max(t) / min(t) < 1.12  # paper: flat


def test_pats_profile_matches_fig10():
    r = run(policy="pats", window=18)
    frac = r.gpu_fraction_by_op()
    # Low-speedup ops mostly on CPU, high-speedup ops mostly on GPU.
    assert frac["morph_open"] < 0.3
    assert frac["bwlabel"] < 0.5
    assert frac["haralick"] > 0.7
    assert frac["recon_to_nuclei"] > 0.7


def test_locality_reduces_transfers_and_time():
    base = run(policy="fcfs", window=16)
    dl = run(policy="fcfs", window=16, locality=True)
    mono = run(policy="fcfs", window=16, pipelined=False)
    assert dl.reuse_hits > dl.reuse_misses  # most assignments reuse data
    assert dl.makespan < base.makespan * 1.01  # no regression vs plain
    # Fig 11: FCFS+DL improves the *non-pipelined* version by ~1.1x.
    assert dl.makespan < mono.makespan * 0.95


def test_prefetch_helps_pats_dl():
    dl = run(policy="pats", window=16, locality=True)
    pf = run(policy="pats", window=16, locality=True, prefetch=True)
    assert pf.makespan <= dl.makespan * 1.01  # paper: ~1.03x


def test_closest_beats_os_placement():
    closest = run(policy="fcfs", window=16)
    os_place = run(policy="fcfs", window=16, placement="os")
    assert closest.makespan < os_place.makespan  # Fig 8


def test_error_sensitivity_matches_fig13():
    base = run(policy="pats", window=18)
    e60 = run(policy="pats", window=18, speedup_error=0.6)
    fcfs = run(policy="fcfs", window=18)
    # <= ~15% degradation at 60% error (paper: ~10%).
    assert e60.makespan < base.makespan * 1.18
    adversarial = run(policy="pats", window=18, speedup_error=1.0)
    # even fully inverted estimates stay within ~15% of FCFS (paper: ~10%).
    assert adversarial.makespan < fcfs.makespan * 1.18


def test_nonpipelined_pats_equals_fcfs():
    # §V-D: monolithic tasks expose no per-op variability to PATS.
    f = run(policy="fcfs", window=16, pipelined=False)
    p = run(policy="pats", window=16, pipelined=False)
    assert abs(p.makespan - f.makespan) / f.makespan < 0.05


def test_node_failure_recovers():
    cfg = SimConfig(
        n_nodes=3, policy="pats", window=14,
        fail_node_at=(1, 5.0), heartbeat_timeout=2.0,
    )
    r = run_simulation(TILES, cfg)
    assert r.completed_ok
    assert r.recovered_leases > 0


def test_straggler_backup_tasks():
    slow = SimConfig(
        n_nodes=3, policy="pats", window=14,
        straggler_factor={2: 8.0}, backup_tasks=True,
    )
    noslow = SimConfig(n_nodes=3, policy="pats", window=14)
    no_backup = SimConfig(
        n_nodes=3, policy="pats", window=14,
        straggler_factor={2: 8.0}, backup_tasks=False,
    )
    r_slow = run_simulation(TILES, slow)
    r_base = run_simulation(TILES, noslow)
    r_nb = run_simulation(TILES, no_backup)
    assert r_slow.completed_ok
    assert r_slow.duplicated_leases > 0
    # Backups cut the straggler tail substantially (92s -> ~50s here)...
    assert r_slow.makespan < r_nb.makespan * 0.75
    # ...and bound it within ~3.5x of a healthy cluster (in-flight ops
    # on the slow node are not preempted, only re-executed).
    assert r_slow.makespan < r_base.makespan * 3.5


def test_multi_node_strong_scaling():
    r2 = run_simulation(240, SimConfig(n_nodes=2, policy="pats", window=15,
                                       locality=True, prefetch=True))
    r8 = run_simulation(240, SimConfig(n_nodes=8, policy="pats", window=15,
                                       locality=True, prefetch=True))
    speedup = r2.makespan / r8.makespan
    assert speedup > 2.7  # >=67% scaling efficiency from 2 to 8 nodes
    # (drain-tail dominated at this reduced tile count.)
