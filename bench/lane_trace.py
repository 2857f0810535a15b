#!/usr/bin/env python3
"""Lane phases of a traced run: the program's spans and counters on the
device trace's clock.

    python3 bench/lane_trace.py --workload <cell> --seed <n> --seconds <s>

Runs the cell's closed loop as ``bench/run.py --trace 1`` does, with a
``Tracer`` handed to the Manager and the worker and the lanes' counters
in the window's counts, and prints as its last line one JSON object:

- ``metrics``: the cell's per-layer metrics and the five readers of the
  lanes (``lane_host_s_per_tile``, ``d2h_gib_per_tile``,
  ``lane_wait_share``, ``idle_in_d2h_share``, ``idle_in_host_share``);
- ``clock``: where the trace's time 0 lies on the wall clock
  (``profile_start_time``) against the stamp taken as ``start_trace``
  returned, and the clock check of ``bench.spans.clock_check``;
- ``idle_s``: the window's device-idle seconds in each lane phase,
  ``idle_by_op`` the same by op, and ``gaps``, the lane phase and op of
  the longest idle gaps;
- ``ops``: device seconds of each op by span attribution, with the
  programs each issued, beside the jit-name split of
  ``bench/metrics/_stages.py``;
- ``cost``: spans per tile, microseconds per span, and host
  nanoseconds per op of the phase counters.

The merged timeline (spans and device programs on one clock) goes to
``--timeline`` as Chrome trace JSON.  No check against the reference is
made: ``bench/run.py`` makes it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as bench_run  # noqa: E402  (stamps T_START)
from bench import spans as sp  # noqa: E402
from bench import tiles as tile_pool  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.metrics import _stages  # noqa: E402
from bench.system import System  # noqa: E402

LANE_METRICS = ("lane_host_s_per_tile.batch", "d2h_gib_per_tile.batch",
                "lane_wait_share.batch", "idle_in_d2h_share.batch",
                "idle_in_host_share.batch")


class TracedSystem(System):
    """The cell's system with a tracer on the Manager and the worker,
    and the lanes' counters in its counts."""

    def __init__(self, config: dict, tracer):
        super().__init__(config)
        self.tracer = self.mgr.tracer = self.rt.tracer = tracer

    def counts(self) -> dict:
        out = super().counts()
        out.update((k, v) for k, v in self.rt.metrics.snapshot().items()
                   if k.startswith(("worker.lane.", "worker.d2h_")))
        out["tracer.spans_dropped"] = self.tracer.spans_dropped
        return out


class SpanCapture(tr.Capture):
    """The device trace, with its time 0 on the wall clock
    (``origin_ns``)."""

    def stop(self) -> tr.Trace:
        import jax

        t1 = time.time_ns()
        jax.profiler.stop_trace()
        found = sorted(self.dir.rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        try:
            trace = tr.Trace.from_xspace(str(found[-1]), (0, t1 - self.t0))
            trace.origin_ns = sp.origin_ns(str(found[-1]))
            return trace
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class SpannedRun(tr.RunData):
    spans: Optional[list] = None


def gap_phases(trace, dev_spans, records, top: int = 10) -> list:
    """The ``top`` longest idle gaps: length, the lane phase holding most
    of each, the op whose phase that is, and the programs around it."""
    phases = sp.phase_intervals(dev_spans)
    names = {r["uid"]: r["name"] for r in records}
    gaps = sorted(((chip, s, e) for chip in trace.devices
                   for s, e in sp.idle(trace, chip)),
                  key=lambda g: g[1] - g[2])[:top]
    rows = []
    for chip, s, e in gaps:
        share = {p: sp.overlap_ns([(s, e)], iv) for p, iv in phases.items()}
        phase = max(share, key=share.get)
        held = [(min(e, he) - max(s, hs), span["args"].get("uid"))
                for name, hs, he, span in dev_spans
                if sp.phase_of(name) == phase and he > s and hs < e]
        uid = max(held, key=lambda h: h[0])[1] if held else None
        rows.append({"s": (e - s) * 1e-9, "at_s": s * 1e-9, "phase": phase,
                     "phase_share": share[phase] / (e - s),
                     "op": names.get(uid), "between": trace.between(chip, s, e)})
    return rows


def idle_by_op(trace, dev_spans, records) -> dict:
    """Device-idle seconds of the window in each op's lane phases,
    ``{op: {phase: s}}``, largest first."""
    names = {r["uid"]: r["name"] for r in records}
    gaps = sorted(g for chip in trace.devices for g in sp.idle(trace, chip))
    d2h = {span["args"].get("uid"): (s, e)
           for name, s, e, span in dev_spans if name == "lane:d2h"}
    out: dict = {}
    for name, s, e, span in dev_spans:
        phase = sp.phase_of(name)
        uid = span["args"].get("uid")
        if phase is None or uid not in names:
            continue
        ns = sp.overlap_ns(gaps, [(s, e)])
        if phase == "commit" and uid in d2h:  # less its nested download
            ns -= sp.overlap_ns(gaps, [d2h[uid]])
        if ns:
            per = out.setdefault(names[uid], {})
            per[phase] = per.get(phase, 0.0) + ns * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -sum(kv[1].values())))


def op_seconds(trace, records) -> dict:
    """Device seconds of each op name by span attribution, with the
    programs under each."""
    names = {r["uid"]: r["name"] for r in records}
    out: dict = {}
    for uid, events in sp.attribute(trace, records).items():
        name = names.get(uid, "before_first_op")
        rec = out.setdefault(name, {"s": 0.0, "programs": {}})
        for prog, s, e in events:
            rec["s"] += (e - s) * 1e-9
            rec["programs"][prog] = rec["programs"].get(prog, 0.0) + (
                e - s) * 1e-9
    for rec in out.values():
        rec["programs"] = dict(sorted(rec["programs"].items(),
                                      key=lambda kv: -kv[1]))
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["s"]))


def span_cost(rt, n: int = 20000) -> dict:
    """Microseconds per lane span (``_lane_span`` into a fresh tracer)
    and host nanoseconds per op of the six phase laps (tracer off)."""
    from repro.core.worker import LANE_PHASES, _PhaseClock
    from repro.telemetry import MetricsRegistry, Tracer

    tracer = Tracer("cost", capacity=n)
    lane = rt._lanes[0]
    root = tracer.start_trace()
    t0 = time.perf_counter()
    for i in range(n):
        rt._lane_span(tracer, lane, "lane:gather", root, (i, i + 1),
                      args={"uid": i})
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    clock = _PhaseClock(MetricsRegistry(), "cost")
    t0 = time.perf_counter_ns()
    for _ in range(n):
        for p in LANE_PHASES:
            clock.lap(p)
    return {"us_per_span": per_span_us,
            "counter_ns_per_op": (time.perf_counter_ns() - t0) / n}


def timeline(trace, spans, path: Path) -> None:
    """Spans and the device programs of the window on one clock, as
    Chrome trace JSON."""
    from repro.telemetry import export_chrome_trace

    events = list(spans)
    for chip, d in trace.devices.items():
        for name, s, e in d.get("modules", []):
            events.append({"name": tr.program_name(name), "cat": "device",
                           "service": f"TPU:{chip}", "tid": "XLA Modules",
                           "ts": (trace.origin_ns + s) * 1e-9,
                           "dur": (e - s) * 1e-9, "args": {}})
    path.parent.mkdir(parents=True, exist_ok=True)
    export_chrome_trace(events, str(path))


def measure(cell: str, seed: int, seconds: float, timeline_path: Path,
            require_chip: bool = True, side: Optional[int] = None) -> dict:
    spec, w, config, mix = bench_run.spec_of(cell)
    side = side or int(config["tile_side"])
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        bench_run.use_compile_cache()
    tiles_ready = tile_pool.start_pool(int(mix["pool"]), side, seed)
    devices, peaks = bench_run.find_chips(int(w["chips"]), require_chip)
    counter = bench_run.CompileCounter()
    tiles, _ = tiles_ready()

    from repro.telemetry import Tracer

    tracer = Tracer("bench", sample_rate=1.0, capacity=1 << 20)
    system = TracedSystem(config, tracer)
    capture = SpanCapture(BENCH / ".cache" / "lane_trace")
    window = bench_run.Window(system, counter, capture)
    jobs, t_open, t_close = bench_run.run_closed(system, mix, tiles, seed,
                                                 seconds, window)
    trace = window.close()
    before, after = window.before, system.counts()
    dropped = after["tracer.spans_dropped"] - before["tracer.spans_dropped"]
    spans = tracer.spans()
    run = SpannedRun(
        cell=cell, config=config, mix=mix, side=side, seconds=seconds,
        t_open=t_open, t_close=t_close, t_end=window.t_end, jobs=jobs,
        before=before, after=after,
        memory_peak=bench_run.peak_memory(devices), peaks=peaks,
        trace=trace, spans=None if dropped else spans)
    completed = [j for j in jobs if j.t_done is not None]

    names = [m["name"] for m in bench_run.cell_metrics(spec, cell,
                                                       "per_layer")]
    metrics = {n: tr.reader(n)(run) for n in names + list(LANE_METRICS)}
    result = {"cell": cell, "seed": seed,
              "tiles_per_s": len(completed) / (t_close - t_open),
              "tiles_in_window": len(completed),
              "spans_dropped": dropped, "metrics": metrics,
              "device": {"busy_s": trace.busy_s(),
                         "window_s": trace.window_s()},
              "clock": {"origin_ns": trace.origin_ns,
                        "origin_minus_start_return_ns": (
                            None if trace.origin_ns is None
                            else trace.origin_ns - capture.t0)}}
    dev = sp.window_spans(run)
    if dev is not None:
        t0, t1 = trace.window
        records = [r for r in sp.ops(dev)
                   if r["sync"][1] > t0 and r["dispatch"][0] < t1]
        split = sp.idle_split(trace, dev)
        result["clock"]["check"] = sp.clock_check(trace, records)
        result["idle_s"] = {p: ns * 1e-9 for p, ns in split.items()}
        result["gaps"] = gap_phases(trace, dev, records)
        result["idle_by_op"] = idle_by_op(trace, dev, records)
        result["ops"] = op_seconds(trace, records)
        result["ops_by_jit_name"] = trace.program_seconds(
            {"segmentation": _stages.SEGMENTATION["programs"],
             "features": _stages.FEATURES["programs"]})
        in_window = sum(1 for _, s, e, _ in dev if e > t0 and s < t1)
        n_tiles = sp.tiles(run)
        result["cost"] = {"spans_in_window": in_window,
                          "spans_per_tile": in_window / n_tiles
                          if n_tiles else None}
        timeline(trace, [x[3] for x in dev], timeline_path)
    result.setdefault("cost", {}).update(span_cost(system.rt))
    result["phase_s"] = {
        p: (sp.lane_ns(run, (p,)) or 0) * 1e-9 for p in sp.PHASES}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--timeline", type=Path,
                    default=BENCH / ".cache" / "lane_trace.json")
    ap.add_argument("--out", type=Path, default=None,
                    help="also write the result object here")
    args = ap.parse_args(argv)
    try:
        result = measure(args.workload, args.seed, args.seconds,
                         args.timeline)
    except bench_run.Refused as e:
        bench_run.log(f"lane_trace: {e}")
        return 2
    line = json.dumps(result)
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
