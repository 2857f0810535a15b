"""The profiler trace of a window, and its reduction to per-layer numbers.

A traced run (``--trace 1``) records the window with JAX's profiler in
this process, which holds the chips.  Only the device is traced: the
host tracer, at any level, also records the runtime's own thread pools
(some 16 M events in a 4096² window), and that slowed the loop it was
meant to watch.  ``Trace`` keeps what the metrics read: per chip, the
device's op events and program (module) events.  All times are
nanoseconds since the profiler's session began, as its trace gives
them; events are clipped to the traced window that ``Capture`` stamps.

The arithmetic lives here so that every change is measured the same
way: busy time is the union of op intervals on a chip, idle share is
one minus busy over the window, a program's group is found by its jit
name, and an unnamed program (an eager dispatch such as
``jit_reduce_window``) belongs to the group of the named program that
ran before it on that chip.  An idle gap is named by the programs on
either side of it: the host's work between those two dispatches.
"""

from __future__ import annotations

import bisect
import importlib.util
import re
import shutil
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")
#: What names a Pallas kernel among a program's ops in the trace.
KERNEL_OP = "custom-call"


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def program_name(name: str) -> str:
    """``jit__watershed_accel(12)`` -> ``jit__watershed_accel``."""
    return _SUFFIX.sub("", name)


@dataclass
class Trace:
    window: tuple[int, int]
    #: chip index -> {"ops": [(name, start, end)], "modules": [...]}
    devices: dict[int, dict[str, list]]

    # -- loading -----------------------------------------------------------

    @classmethod
    def from_xspace(cls, path: str, window: tuple[int, int]) -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(str(path))
        devices: dict[int, dict[str, list]] = {}
        for plane in pd.planes:
            m = _DEVICE.match(plane.name)
            if not m:
                continue
            d = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = ("ops" if line.name == "XLA Ops" else
                       "modules" if line.name == "XLA Modules" else None)
                if key is None:
                    continue
                d[key].extend((e.name, int(e.start_ns), int(e.end_ns))
                              for e in line.events)
        return cls(window, devices).clipped()

    def clipped(self) -> "Trace":
        """Events inside the window only, cut at its edges."""
        t0, t1 = self.window

        def clip(events):
            return sorted(((n, max(s, t0), min(e, t1)) for n, s, e in events
                           if e > t0 and s < t1), key=lambda ev: ev[1])

        devices = {i: {k: clip(v) for k, v in d.items()}
                   for i, d in self.devices.items()}
        return Trace(self.window, devices)

    def to_json(self) -> dict:
        return {"window": list(self.window),
                "devices": {str(i): d for i, d in self.devices.items()}}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(tuple(obj["window"]),
                   {int(i): {k: [tuple(e) for e in v] for k, v in d.items()}
                    for i, d in obj["devices"].items()}).clipped()

    # -- reduction ---------------------------------------------------------

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy_events(self, chip: int) -> list:
        d = self.devices.get(chip, {})
        return d.get("ops") or d.get("modules") or []

    def busy(self, chip: int) -> list[tuple[int, int]]:
        return union((s, e) for _, s, e in self._busy_events(chip))

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips traced."""
        if not self.devices:
            return 0.0
        total = sum(e - s for i in self.devices for s, e in self.busy(i))
        return total * 1e-9 / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def program_seconds(self, groups: dict[str, tuple[str, ...]]) -> dict:
        """Device seconds of the programs of each group, summed over
        chips.  A program joins the first group with a pattern in its
        name; one matching none joins the group of the program before it
        on its chip (``"other"`` at the start)."""
        out = {g: 0.0 for g in groups}
        out["other"] = 0.0
        for d in self.devices.values():
            current = "other"
            for name, s, e in d.get("modules", []):
                for g, patterns in groups.items():
                    if any(p in name for p in patterns):
                        current = g
                        break
                out[current] += (e - s) * 1e-9
        return out

    def kernel_seconds(self, program: str) -> float:
        """Device seconds of the Pallas kernels (``KERNEL_OP`` in the op
        name) run inside programs whose name contains ``program``."""
        total = 0
        for d in self.devices.values():
            mods = d.get("modules", [])
            starts = [s for _, s, _ in mods]
            for name, s, e in d.get("ops", []):
                if KERNEL_OP not in name:
                    continue
                k = bisect.bisect_right(starts, s) - 1
                if k >= 0 and program in mods[k][0] and mods[k][2] >= e:
                    total += e - s
        return total * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (named ``program/op``), and
        the longest idle gaps, each named by the programs on either side
        of it (``program -> program``)."""
        per_op: dict[str, float] = {}
        for d in self.devices.values():
            mods = d.get("modules", [])
            starts = [s for _, s, _ in mods]
            for name, s, e in d.get("ops", []):
                k = bisect.bisect_right(starts, s) - 1
                prog = (program_name(mods[k][0])
                        if k >= 0 and mods[k][2] >= e else "?")
                key = f"{prog}/{name}"
                per_op[key] = per_op.get(key, 0.0) + (e - s) * 1e-9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

        gaps = []
        for chip in self.devices:
            busy = self.busy(chip)
            edges = [self.window[0]] + [x for iv in busy for x in iv] + [self.window[1]]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((self.between(chip, s, e), s, e))
        gaps = sorted(gaps, key=lambda g: g[1] - g[2])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[name, (e - s) * 1e-9] for name, s, e in gaps]}

    def between(self, chip: int, s: int, e: int) -> str:
        """``before -> after``: the programs that ended last at or before
        ``s`` and started first at or after ``e`` on ``chip`` (``start``
        and ``end`` at the window's edges)."""
        mods = self.devices.get(chip, {}).get("modules", [])
        before = [n for n, _, me in mods if me <= s]
        after = [n for n, ms, _ in mods if ms >= e]
        return (f"{program_name(before[-1]) if before else 'start'} -> "
                f"{program_name(after[0]) if after else 'end'}")


class Capture:
    """Device trace of this process, read back as a :class:`Trace`.

    The window runs from the session's start (time 0 of the trace) for
    as long as the host clock saw between ``start_trace`` returning and
    ``stop_trace`` being called: it lies wholly inside what the
    profiler recorded, short of it by at most the tail of the start
    call."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.t0 = time.time_ns()

    def stop(self) -> Trace:
        import jax

        t1 = time.time_ns()
        jax.profiler.stop_trace()
        found = sorted(self.dir.rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        try:
            return Trace.from_xspace(str(found[-1]), (0, t1 - self.t0))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class RunData:
    """What a per-layer metric's reader gets: the window, its jobs, the
    program's counts before and after it, the peak device memory, the
    chip's published peaks, and the trace of a traced run."""

    cell: str
    config: dict
    mix: dict
    side: int
    seconds: float
    t_open: float
    t_close: float
    t_end: float
    jobs: list
    before: dict
    after: dict
    memory_peak: Optional[int]
    peaks: Optional[dict]
    trace: Optional[Trace]

    def runs(self) -> dict:
        """Ops run in the window by ``op/kind`` (``variant_runs`` delta)."""
        return delta(self.before, self.after)


def delta(before: dict, after: dict) -> dict:
    b = before["variant_runs"]
    return {k: n - b.get(k, 0) for k, n in after["variant_runs"].items()
            if n - b.get(k, 0)}


def reader(name: str):
    """The ``read(run) -> value or None`` of ``bench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
