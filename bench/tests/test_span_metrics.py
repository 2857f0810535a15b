"""The lanes' spans on the device trace's clock and the five readers of
the lanes' spans and counters, on a hand-made trace and hand-made spans;
the clock anchor of a trace recorded on the CPU; and a whole traced run
of ``bench/lane_trace.py`` at a small tile side on the CPU."""

import time

import pytest

from bench import spans as sp, trace as tr

U = 100_000             # the hand-made times' unit, 0.1 ms, in ns
ORIGIN = 10**15         # the trace's time 0 on the wall clock, in ns


def _span(name, start, end, **args):
    return {"name": name, "ts": (ORIGIN + start * U) * 1e-9,
            "dur": (end - start) * U * 1e-9, "args": args,
            "trace": "t", "span": name, "parent": None}


#: Two ops on one lane (times in units ``U`` on the device clock): device
#: busy 200-400 and 600-800 of a 0-1000 window, so idle 600.
SPANS = [
    _span("lane:wait", 0, 100),
    _span("lane:gather", 100, 150, uid=1),
    _span("op:a", 150, 250, uid=1),
    _span("lane:sync", 250, 400, uid=1),
    _span("lane:commit", 400, 550, uid=1),
    _span("lane:d2h", 420, 500, uid=1, bytes=64),
    _span("lane:wait", 550, 560),
    _span("lane:gather", 560, 580, uid=2),
    _span("op:b", 580, 620, uid=2),
    _span("lane:sync", 620, 800, uid=2),
    _span("lane:commit", 800, 950, uid=2),
    _span("lane:d2h", 800, 900, uid=2, bytes=64),
    _span("stage:queued", 0, 100, uid=7),
]
#: Idle units in each phase, by hand: 0-200 is wait 100, gather
#: 50, dispatch 50; 400-600 commit 20+50, d2h 80, wait 10, gather 20,
#: dispatch 20; 800-1000 d2h 100, commit 50, in no span 50.
IDLE = {"wait": 110, "gather": 70, "dispatch": 70, "sync": 0, "d2h": 180,
        "commit": 120, "none": 50}


def _trace(modules=None):
    t = tr.Trace((0, 1000 * U), {0: {
        "ops": [("fusion", 200 * U, 400 * U), ("fusion", 600 * U, 800 * U)],
        "modules": modules or [("jit__a(1)", 200 * U, 380 * U),
                               ("jit_reshape(2)", 380 * U, 400 * U),
                               ("jit__b(3)", 600 * U, 800 * U)],
    }})
    t.origin_ns = ORIGIN
    return t


def _counters(phase_units, d2h_bytes):
    out = {f"worker.lane.tpu0.{p}_ns": n * U for p, n in phase_units.items()}
    out["worker.d2h_bytes"] = d2h_bytes
    return out


def _run(spans=SPANS, counters=True):
    runs = {"rbc_detection/tpu": 2, "morph_open/tpu": 2,
            "feature_fused/tpu": 2}
    before = {"variant_runs": {}, "host_fallbacks": 0, "errors": 0}
    after = {"variant_runs": runs, "host_fallbacks": 0, "errors": 0}
    if counters:
        before.update(_counters(dict.fromkeys(sp.PHASES, 1000), 1000))
        after.update(_counters(
            {"wait": 1200, "gather": 1100, "dispatch": 1500, "sync": 3000,
             "d2h": 1400, "commit": 1250}, 1000 + 3 * 2**30))
    run = tr.RunData(
        cell="c", config={"variants": {"accel_kind": "tpu"}}, mix={},
        side=128, seconds=1.0, t_open=0.0, t_close=1.0, t_end=1.0, jobs=[],
        before=before, after=after, memory_peak=None, peaks=None,
        trace=_trace())
    run.spans = spans
    return run


def test_spans_land_on_the_device_clock():
    dev = sp.on_device_clock(SPANS, ORIGIN)
    assert [(n, s, e) for n, s, e, _ in dev][:3] == [
        ("lane:wait", 0, 100 * U), ("stage:queued", 0, 100 * U),
        ("lane:gather", 100 * U, 150 * U)]


def test_idle_split_over_the_phases_by_hand():
    split = sp.idle_split(_trace(), sp.on_device_clock(SPANS, ORIGIN))
    assert split == {p: n * U for p, n in IDLE.items()}


@pytest.mark.parametrize("name, expected", [
    # (d2h) / idle
    ("idle_in_d2h_share.batch", 100.0 * 180 / 600),
    # (gather + dispatch + commit less d2h) / idle
    ("idle_in_host_share.batch", 100.0 * (70 + 70 + 120) / 600),
    # (gather + dispatch + d2h + commit deltas) / 2 tiles
    ("lane_host_s_per_tile.batch", (100 + 500 + 400 + 250) * U * 1e-9 / 2),
    ("d2h_gib_per_tile.batch", 3 / 2),
    # wait delta over all six deltas
    ("lane_wait_share.batch",
     100.0 * 200 / (200 + 100 + 500 + 2000 + 400 + 250)),
])
def test_lane_readers_by_hand(name, expected):
    assert tr.reader(name)(_run()) == pytest.approx(expected)


@pytest.mark.parametrize("name", [
    "idle_in_d2h_share.batch", "idle_in_host_share.batch",
    "lane_host_s_per_tile.batch", "d2h_gib_per_tile.batch",
    "lane_wait_share.batch"])
def test_lane_readers_read_nothing_where_the_run_has_nothing(name):
    run = _run(spans=None, counters=False)
    assert tr.reader(name)(run) is None
    run.trace.origin_ns = None
    assert tr.reader(name)(run) is None


def test_programs_belong_to_the_op_that_issued_them():
    dev = sp.on_device_clock(SPANS, ORIGIN)
    records = sp.ops(dev)
    assert [(r["uid"], r["name"]) for r in records] == [(1, "a"), (2, "b")]
    assert records[0]["sync"] == (250 * U, 400 * U)
    got = sp.attribute(_trace(), records)
    # The eager reshape after op a's program is op a's.
    assert [p for p, _, _ in got[1]] == ["jit__a", "jit_reshape"]
    assert [p for p, _, _ in got[2]] == ["jit__b"]
    check = sp.clock_check(_trace(), records)
    assert check["ops_within"] == 1.0 and check["busy_outside"] == 0.0


def test_idle_gaps_are_named_by_their_phase_and_op():
    from bench import lane_trace

    dev = sp.on_device_clock(SPANS, ORIGIN)
    rows = lane_trace.gap_phases(_trace(), dev, sp.ops(dev))
    assert [(round(r["at_s"] * 1e9 / U), r["phase"], r["op"])
            for r in rows] == [(0, "wait", None), (400, "d2h", "a"),
                               (800, "d2h", "b")]
    assert rows[1]["phase_share"] == pytest.approx(80 / 200)


def test_idle_by_op_and_phase_by_hand():
    from bench import lane_trace

    dev = sp.on_device_clock(SPANS, ORIGIN)
    got = lane_trace.idle_by_op(_trace(), dev, sp.ops(dev))
    want = {"a": {"gather": 50, "dispatch": 50, "commit": 70, "d2h": 80},
            "b": {"gather": 20, "dispatch": 20, "commit": 50, "d2h": 100}}
    assert got == {op: {p: pytest.approx(n * U * 1e-9) for p, n in ph.items()}
                   for op, ph in want.items()}


def test_clock_check_fails_on_a_shifted_clock():
    shifted = sp.on_device_clock(SPANS, ORIGIN + 150 * U)
    check = sp.clock_check(_trace(), sp.ops(shifted))
    assert check["ops_within"] < 1.0
    assert check["busy_outside"] > 0.0


def test_origin_of_a_cpu_trace_lies_between_the_host_stamps(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    before = time.time_ns()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    after = time.time_ns()
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    assert before <= sp.origin_ns(str(path)) <= after


def test_traced_run_on_the_cpu_reads_the_lanes(tmp_path):
    from bench import lane_trace

    r = lane_trace.measure("gbm4k.batch", 2**31 + 77, 2.0,
                           tmp_path / "timeline.json",
                           require_chip=False, side=128)
    assert r["spans_dropped"] == 0 and r["tiles_in_window"] > 0
    m = r["metrics"]
    assert m["lane_host_s_per_tile.batch"] > 0
    assert m["d2h_gib_per_tile.batch"] > 0
    assert 0 <= m["lane_wait_share.batch"] < 100
    # The CPU trace has no device planes: no idle to split.
    assert m["idle_in_d2h_share.batch"] is None
    assert r["clock"]["origin_ns"] is not None
    assert r["cost"]["spans_per_tile"] > 0
    assert sum(r["phase_s"].values()) > 0
    assert (tmp_path / "timeline.json").exists()
