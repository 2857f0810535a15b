"""The reduction from a profiler trace to per-layer numbers, checked on
a small trace recorded on a TPU v5e (``data/trace_small.json``: six
seconds of a traced window of 512² tiles) and on hand-made events."""

import json
from pathlib import Path

import pytest

from bench import roofline, run, trace as tr
from bench.metrics import _stages

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def recorded():
    return tr.Trace.from_json(json.loads(DATA.read_text()))


def brute_busy_ns(events, t0, t1):
    """Busy time by sweeping every event boundary (no merging)."""
    points = sorted({t0, t1, *(x for _, s, e in events for x in (s, e))})
    busy = 0
    for a, b in zip(points, points[1:]):
        if any(s <= a and b <= e for _, s, e in events):
            busy += b - a
    return busy


def test_recorded_trace_has_device_events(recorded):
    assert recorded.devices
    for d in recorded.devices.values():
        assert d["ops"] and d["modules"]


def test_busy_is_the_union_of_op_intervals(recorded):
    t0, t1 = recorded.window
    for chip, d in recorded.devices.items():
        merged = sum(e - s for s, e in recorded.busy(chip))
        assert merged == brute_busy_ns(d["ops"], t0, t1)
        assert merged <= sum(e - s for _, s, e in d["ops"])
    share = recorded.idle_share()
    assert 0.0 < share < 1.0
    assert share == pytest.approx(1 - recorded.busy_s() / recorded.window_s())


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 9), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        (0, 4), (5, 9), (10, 11)]


def test_programs_group_by_jit_name_and_eager_dispatches_inherit():
    t = tr.Trace((0, 100), {0: {"ops": [], "modules": [
        ("jit_reduce_window(3)", 0, 5),        # before any named program
        ("jit__fill_holes_accel(7)", 10, 30),
        ("jit_subtract(1)", 30, 32),           # eager: inherits segmentation
        ("jit_feature_fused_pallas(2)", 40, 60),
        ("jit_scatter_add(4)", 60, 70),        # eager: inherits features
    ]}})
    secs = t.program_seconds({"segmentation": _stages.SEGMENTATION["programs"],
                              "features": _stages.FEATURES["programs"]})
    assert secs["other"] == pytest.approx(5e-9)
    assert secs["segmentation"] == pytest.approx(22e-9)
    assert secs["features"] == pytest.approx(30e-9)


def test_recorded_programs_are_all_accounted(recorded):
    groups = {"segmentation": _stages.SEGMENTATION["programs"],
              "features": _stages.FEATURES["programs"]}
    secs = recorded.program_seconds(groups)
    total = sum(e - s for d in recorded.devices.values()
                for _, s, e in d["modules"]) * 1e-9
    assert sum(secs.values()) == pytest.approx(total)
    assert secs["segmentation"] > 0 and secs["features"] > 0


def test_roofline_share_from_the_peaks_table():
    peak = run.peaks_for("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    flops, nbytes = roofline.morph_recon(4096)
    least, bound = roofline.least_time(flops, nbytes, peak)
    assert bound == "bytes"
    assert least == pytest.approx(8 * 4096 * 4096 / 819e9)
    # Two calls, and 1 ms of kernel ops inside the kernel's program.
    kernel_op = f"fusion.1/{tr.KERNEL_OP}"
    t = tr.Trace((0, 10**7), {0: {
        "modules": [("jit_morph_recon_pallas(1)", 0, 10**6),
                    ("jit__fill_holes_accel(2)", 10**6, 2 * 10**6)],
        "ops": [(kernel_op, 0, 4 * 10**5), (kernel_op, 5 * 10**5, 10**6),
                (kernel_op, 10**6, 2 * 10**6)]}})
    assert t.kernel_seconds("morph_recon") == pytest.approx(9e-4)

    class Run:
        config = {"variants": {"accel_kind": "tpu"}}
        side, peaks, trace = 4096, peak, t

        def runs(self):
            return {"recon_to_nuclei/tpu": 2}

    share = _stages.kernel_share(Run(), "recon_to_nuclei", "morph_recon",
                                 roofline.morph_recon)
    assert share == pytest.approx(100 * 2 * least / 9e-4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.Refused):
        run.peaks_for("TPU v0 imaginary")


def test_json_round_trip(recorded):
    again = tr.Trace.from_json(json.loads(json.dumps(recorded.to_json())))
    assert again.busy_s() == recorded.busy_s()
    assert again.breakdown() == recorded.breakdown()


def test_idle_gaps_are_named_by_the_programs_around_them():
    t = tr.Trace((0, 100), {0: {
        "modules": [("jit__fill_holes_accel(7)", 10, 30),
                    ("jit__bwlabel_accel(2)", 60, 90)],
        "ops": [("fusion.1", 10, 30), ("fusion.2", 60, 90)]}})
    gaps = t.breakdown()["idle_gaps"]
    assert [name for name, _ in gaps] == [
        "jit__fill_holes_accel -> jit__bwlabel_accel",
        "start -> jit__fill_holes_accel",
        "jit__bwlabel_accel -> end"]
    assert [s for _, s in gaps] == pytest.approx([30e-9, 10e-9, 10e-9])
    assert t.busy_s() + sum(s for _, s in gaps) == pytest.approx(t.window_s())


def test_events_outside_the_window_are_cut():
    t = tr.Trace((100, 200), {0: {
        "modules": [("jit_a(1)", 50, 150), ("jit_b(1)", 190, 260),
                    ("jit_c(1)", 300, 400)],
        "ops": [("x", 50, 150), ("y", 190, 260), ("z", 300, 400)]}}).clipped()
    assert t.devices[0]["ops"] == [("x", 100, 150), ("y", 190, 200)]
    assert t.busy_s() == pytest.approx(60e-9)


def test_capture_window_is_on_the_trace_clock(tmp_path):
    """The window starts at the trace's time 0 and lasts what the host
    clock saw between the start and the stop."""
    import time

    cap = tr.Capture(tmp_path / "trace")
    cap.start()
    time.sleep(0.2)
    t = cap.stop()
    assert t.window[0] == 0
    assert 0.2 <= t.window_s() < 5.0
    assert not (tmp_path / "trace").exists()
