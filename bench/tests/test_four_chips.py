"""The four-chip cell (``gbm4k-4chip.batch``): a whole run at a small
tile side on four virtual CPU devices, and its three readers
(``device_idle_share.4chip``, ``chip_idle_spread.4chip``,
``peak_hbm_gib.4chip``) on hand-made four-chip traces and on one
recorded on a four-chip TPU v5e host (``data/trace_4chip.json``: 0.3 s
of a traced window of the cell)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import trace as tr

ROOT = Path(__file__).resolve().parents[2]
CELL = "gbm4k-4chip.batch"
READERS = ("device_idle_share.4chip", "chip_idle_spread.4chip",
           "peak_hbm_gib.4chip")

RUN_SCRIPT = textwrap.dedent(
    f"""
    import json, sys
    sys.path.insert(0, "src")
    import jax
    from bench import run
    assert len(jax.devices()) == 4
    out = {{}}
    for trace in (False, True):
        out[str(trace)] = run.measure("{CELL}", 2**31 + 515, 2.0, trace,
                                      require_chip=False, side=128)
    print(json.dumps(out))
    """
)


def test_four_chip_cell_is_correct_on_four_cpu_devices():
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    out = subprocess.run([sys.executable, "-c", RUN_SCRIPT], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    runs = json.loads(out.stdout.strip().splitlines()[-1])
    plain, traced = runs["False"], runs["True"]
    for r in (plain, traced):
        assert r["correct"] and r["failed"] == 0, r["checks"]
        assert r["device"]["count"] == 4
    assert set(plain["metrics"]) == {"tiles_per_s", "setup_s"}
    # The CPU's trace has no device planes: the readers find nothing.
    assert not set(READERS) & set(traced["metrics"])


def test_four_chip_check_samples_every_lane_many_times():
    """DL keeps a tile on one chip, so ``correct`` checks every chip
    only if the sample holds several tiles a lane: 4 a lane cover all
    four of evenly shared lanes 96 % of the time."""
    from bench import compare
    from bench.run import spec_of

    _, _, config, _ = spec_of(CELL)
    assert compare.limits(CELL)["sample"] >= 4 * len(config["lanes"])


U = 1_000_000  # ns
RECORDED = Path(__file__).resolve().parent / "data" / "trace_4chip.json"


def _run(devices, memory_peak=None, trace=None):
    window = (0, 100 * U)
    trace = trace or tr.Trace(
        window, {c: {"ops": [(f"op{c}", s * U, e * U) for s, e in iv],
                     "modules": []}
                 for c, iv in devices.items()}).clipped()
    return tr.RunData(
        cell=CELL, config={"lanes": [["tpu", i] for i in range(4)]}, mix={},
        side=128, seconds=1.0, t_open=0.0, t_close=1.0, t_end=1.0, jobs=[],
        before={}, after={}, memory_peak=memory_peak, peaks=None,
        trace=trace)


def test_readers_by_hand():
    # Busy 90, 80 (overlapping ops merge), 60 and 70 of a 100 window.
    run = _run({0: [(0, 90)], 1: [(0, 50), (40, 80)], 2: [(20, 80)],
                3: [(0, 30), (50, 90)]}, memory_peak=3 * 2**30)
    idle = [10, 20, 40, 30]
    assert tr.reader("device_idle_share.4chip")(run) == pytest.approx(
        sum(idle) / 4)
    assert tr.reader("chip_idle_spread.4chip")(run) == pytest.approx(30)
    assert tr.reader("peak_hbm_gib.4chip")(run) == 3.0


def test_a_chip_that_ran_nothing_is_idle_throughout():
    run = _run({0: [(0, 50)], 1: [(0, 50)], 2: [(0, 50)]})
    assert tr.reader("device_idle_share.4chip")(run) == pytest.approx(62.5)
    assert tr.reader("chip_idle_spread.4chip")(run) == pytest.approx(50)


def test_readers_read_nothing_where_the_run_has_nothing():
    run = _run({})
    for name in READERS:
        assert tr.reader(name)(run) is None
    run.trace = None
    assert tr.reader("device_idle_share.4chip")(run) is None


def test_readers_on_a_recorded_four_chip_trace():
    trace = tr.Trace.from_json(json.loads(RECORDED.read_text()))
    assert sorted(trace.devices) == [0, 1, 2, 3]
    run = _run({}, memory_peak=6808931328, trace=trace)
    t0, t1 = trace.window
    idle = [100.0 * (1 - sum(e - s for s, e in trace.busy(c)) / (t1 - t0))
            for c in range(4)]
    assert all(0.0 < share < 100.0 for share in idle)
    mean = tr.reader("device_idle_share.4chip")(run)
    assert mean == pytest.approx(sum(idle) / 4)
    assert mean == pytest.approx(100.0 * trace.idle_share())
    spread = tr.reader("chip_idle_spread.4chip")(run)
    assert spread == pytest.approx(max(idle) - min(idle)) and spread > 0
    assert tr.reader("peak_hbm_gib.4chip")(run) == 6808931328 / 2**30


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_bfloat16_control_is_not_correct_in_the_four_chip_cell(seed):
    """The limits are ``gbm4k.batch``'s, and hold the same control out."""
    import jax.numpy as jnp

    from bench import compare
    from bench.references import wsi_pipeline as reference
    from bench.tiles import synth_tile

    tile = synth_tile(0, size=256, seed=seed)
    numbers = {**compare.tile_numbers(reference.run(tile, dtype=jnp.bfloat16),
                                      reference.run(tile)),
               "failed": 0, "host_fallbacks": 0, "worker_errors": 0}
    assert not compare.judge(numbers, compare.limits(CELL)), numbers
