"""The program's spans on the device trace's clock, and the lane phases
that the device's idle time falls in.

A traced run can record, besides the device trace, the spans of the
worker lanes and the coordinator (``repro.telemetry.Tracer``): one span
per lane phase per op (``lane:wait``, ``lane:gather``, ``op:<name>`` for
the dispatch, ``lane:sync``, ``lane:d2h`` nested in ``lane:commit``) and
the stages' ``stage:queued`` and ``stage:lease``.  Span times are
wall-clock seconds; the device trace counts nanoseconds from its
session's start, which the trace's ``Task Environment`` plane stamps on
the wall clock (``profile_start_time``, :func:`origin_ns`).  A span then
lies on the device clock at ``[round(ts * 1e9) - origin, ... + dur]``.

The lane is serial and waits for every written-back output before its
next op, so each device program belongs to the op whose dispatch-to-sync
interval holds it (:func:`attribute`), and each idle nanosecond of the
device falls in one lane phase (:func:`idle_split`).

The per-layer readers of these spans and of the lanes' counters
(``worker.lane.<lane>.<phase>_ns``, ``worker.d2h_bytes``) read from a
run: ``run.spans`` (``None`` when the tracer's buffer dropped any),
``run.trace.origin_ns``, and the counters in ``run.before`` and
``run.after``.  Where a run carries none of them they return ``None``.
"""

from __future__ import annotations

import bisect
from typing import Optional

from bench.trace import program_name, union

#: The lane phases (``repro.core.worker.LANE_PHASES``), by span name.
PHASES = ("wait", "gather", "dispatch", "sync", "d2h", "commit")
_SPAN_PHASE = {"lane:wait": "wait", "lane:gather": "gather",
               "lane:sync": "sync", "lane:d2h": "d2h",
               "lane:commit": "commit"}
#: Phases in which the host, not the device or the queue, holds the lane.
HOST_PHASES = ("gather", "dispatch", "commit")
#: Slack of the clock check, in nanoseconds.
SLACK_NS = 1_000_000


def origin_ns(xplane_path: str) -> Optional[int]:
    """``profile_start_time`` of a profiler session (wall-clock epoch
    nanoseconds), from the trace's ``Task Environment`` plane."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(xplane_path)).planes:
        if plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    return int(value)
    return None


def phase_of(name: str) -> Optional[str]:
    if name.startswith("op:"):
        return "dispatch"
    return _SPAN_PHASE.get(name)


def on_device_clock(spans, origin: int) -> list[tuple[str, int, int, dict]]:
    """``(name, start, end, span)`` on the device trace's clock, sorted
    by start."""
    out = []
    for s in spans:
        start = round(s["ts"] * 1e9) - origin
        out.append((s["name"], start, start + round(s["dur"] * 1e9), s))
    return sorted(out, key=lambda x: x[1])


def subtract(a, b) -> list[tuple[int, int]]:
    """Merged intervals ``a`` less merged intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def overlap_ns(a, b) -> int:
    """Nanoseconds in both of two merged interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def phase_intervals(dev_spans) -> dict[str, list[tuple[int, int]]]:
    """Merged intervals of each lane phase; ``commit`` less the ``d2h``
    spans nested in it, so the phases are disjoint as the lane's
    counters are."""
    raw: dict[str, list] = {p: [] for p in PHASES}
    for name, s, e, _ in dev_spans:
        p = phase_of(name)
        if p is not None:
            raw[p].append((s, e))
    out = {p: union(v) for p, v in raw.items()}
    out["commit"] = subtract(out["commit"], out["d2h"])
    return out


def idle(trace, chip: int) -> list[tuple[int, int]]:
    """The traced window's intervals in which no op ran on ``chip``."""
    return subtract([trace.window], trace.busy(chip))


def idle_split(trace, dev_spans) -> dict[str, int]:
    """Device-idle nanoseconds of the window in each lane phase, summed
    over chips, and ``"none"`` for idle time in no phase span."""
    phases = phase_intervals(dev_spans)
    out = {p: 0 for p in PHASES}
    out["none"] = 0
    for chip in trace.devices:
        gaps = idle(trace, chip)
        total = sum(e - s for s, e in gaps)
        for p, iv in phases.items():
            out[p] += overlap_ns(gaps, iv)
        out["none"] += total - sum(overlap_ns(gaps, iv)
                                   for iv in phases.values())
    return out


def ops(dev_spans) -> list[dict]:
    """One record per op of the spans (``args.uid``): its name and its
    ``gather``, ``dispatch`` and ``sync`` intervals on the device clock,
    sorted by dispatch (an op not synced has a sync of no length at the
    end of its dispatch)."""
    recs: dict = {}
    for name, s, e, span in dev_spans:
        p = phase_of(name)
        uid = (span.get("args") or {}).get("uid")
        if uid is None or p not in ("gather", "dispatch", "sync"):
            continue
        rec = recs.setdefault(uid, {"uid": uid})
        rec[p] = (s, e)
        if p == "dispatch":
            rec["name"] = name[3:]
    out = []
    for rec in recs.values():
        if "dispatch" not in rec:
            continue
        lo, hi = rec["dispatch"]
        rec.setdefault("gather", (lo, lo))
        rec.setdefault("sync", (hi, hi))
        out.append(rec)
    return sorted(out, key=lambda r: r["dispatch"][0])


def attribute(trace, op_records) -> dict[int, list[tuple[str, int, int]]]:
    """Device programs (module events) of the window by the op that
    issued them: the op whose dispatch began last at or before the
    program began (less the clock check's slack); ``-1`` holds those
    that began before any op."""
    starts = [r["dispatch"][0] - SLACK_NS for r in op_records]
    out: dict[int, list] = {}
    for d in trace.devices.values():
        for name, s, e in d.get("modules", []):
            k = bisect.bisect_right(starts, s) - 1
            uid = op_records[k]["uid"] if k >= 0 else -1
            out.setdefault(uid, []).append((program_name(name), s, e))
    return out


def clock_check(trace, op_records) -> dict:
    """Whether the device clock and the spans agree.

    ``ops_within``: share of the ops each of whose attributed programs
    lies within ``[dispatch start - 1 ms, sync end + 1 ms]``; ``late``
    lists the others (op, its dispatch start in s, the program, its
    start and end less the sync's end in ms).  ``busy_outside``: share
    of device busy time outside every op's ``[gather start, sync end]``.
    ``offset_ms`` bounds how far the device's clock sits from the
    spans' over the ops within: a program starts after its op's
    dispatch began and ends before its sync returned, so the offset
    (device clock less the spans') lies in ``[-min lag, min lead]``,
    ``lead`` the first program's start less the dispatch's, ``lag`` the
    sync's end less the last program's end, over ops not cut by the
    window's edges."""
    attributed = attribute(trace, op_records)
    t0, t1 = trace.window
    late, leads, lags = [], [], []
    for r in op_records:
        lo = r["dispatch"][0] - SLACK_NS
        hi = r["sync"][1] + SLACK_NS
        events = attributed.get(r["uid"], [])
        bad = [(p, s, e) for p, s, e in events if s < lo or e > hi]
        if bad:
            p, s, e = max(bad, key=lambda ev: ev[2])
            end = r["sync"][1]
            late.append([r["name"], r["dispatch"][0] * 1e-9, p,
                         (s - end) * 1e-6, (e - end) * 1e-6])
            continue
        if events and r["dispatch"][0] > t0:
            leads.append(min(s for _, s, _ in events) - r["dispatch"][0])
        if events and r["sync"][1] < t1:
            lags.append(r["sync"][1] - max(e for _, _, e in events))
    covered = union((r["gather"][0], r["sync"][1]) for r in op_records)
    busy = outside = 0
    for chip in trace.devices:
        b = trace.busy(chip)
        busy += sum(e - s for s, e in b)
        outside += sum(e - s for s, e in subtract(b, covered))
    n = len(op_records)
    return {"ops": n,
            "ops_within": (n - len(late)) / n if n else None,
            "busy_outside": outside / busy if busy else None,
            "before_first_op": len(attributed.get(-1, [])),
            "late": late[:10],
            "offset_ms": ([-min(lags) * 1e-6, min(leads) * 1e-6]
                          if leads and lags else None)}


# -- what the per-layer readers read -------------------------------------


def window_spans(run):
    """The run's spans on its device trace's clock, or ``None`` where
    the run has no spans, no clock anchor, or a buffer that dropped
    spans."""
    spans = getattr(run, "spans", None)
    trace = run.trace
    origin = getattr(trace, "origin_ns", None) if trace is not None else None
    if not spans or origin is None:
        return None
    return on_device_clock(spans, origin)


def idle_share(run, phases) -> Optional[float]:
    """Percent of the window's device-idle time inside spans of
    ``phases``."""
    dev = window_spans(run)
    if dev is None:
        return None
    split = idle_split(run.trace, dev)
    total = sum(split.values())
    if total <= 0:
        return None
    return 100.0 * sum(split[p] for p in phases) / total


def counter_delta(run, suffixes) -> Optional[int]:
    """Window delta of the counters whose names end in any of
    ``suffixes``, summed (``None`` where the run carries none)."""
    keys = [k for k in run.after
            if isinstance(k, str) and k.endswith(tuple(suffixes))]
    if not keys:
        return None
    return sum(run.after[k] - run.before.get(k, 0) for k in keys)


def lane_ns(run, phases) -> Optional[int]:
    return counter_delta(run, [f".{p}_ns" for p in phases])


def tiles(run) -> Optional[float]:
    """Tiles' worth of ops run in the window: the runs of the pipeline's
    ops that ran at all, averaged (as ``_stages.device_s_per_tile``)."""
    from bench.metrics._stages import FEATURES, SEGMENTATION

    kind = run.config["variants"]["accel_kind"]
    runs = run.runs()
    counts = [runs[f"{op}/{kind}"]
              for op in SEGMENTATION["ops"] + FEATURES["ops"]
              if runs.get(f"{op}/{kind}")]
    return sum(counts) / len(counts) if counts else None
