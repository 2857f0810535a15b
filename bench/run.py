#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It builds the cell's system from its
configuration file (``bench/configs``), makes the tile pool from the
seed, warms up every program the cell's traffic uses, drives the
traffic mix (``bench/traffic``) for ``--seconds``, checks a sample of
what the timed path produced against the plain reference
(``bench/references``), and prints as the last line of standard output
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and, traced, ``breakdown``), then ``checks``, each number
compared beside its limit.  With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics,
each read by its own file in ``bench/metrics``.

It exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or a device kind missing from
``bench/peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(Exception):
    """The run cannot measure here: no result line is printed."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def spec_of(cell: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark spec, workload entry, configuration, traffic mix)."""
    from bench import traffic

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell not in cells:
        raise Refused(f"no workload {cell!r} in BENCHMARK.json")
    w = cells[cell]
    config = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    return spec, w, config, traffic.load(w["traffic"])


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed place inside the checkout, for
    every program however fast it compiles; the program's own
    ``enable_compile_cache`` takes the same directory from the
    environment."""
    path = BENCH / ".cache" / "jax"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    # libtpu's own logs, which otherwise go to a fixed path in /tmp.
    os.environ.setdefault("TPU_LOG_DIR", str(BENCH / ".cache" / "tpu_logs"))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return str(path)


def peaks_for(kind: str) -> dict:
    """Published peaks of a device kind; an unknown kind is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def find_chips(chips: int, require_chip: bool = True):
    """The chips this run uses and their published peaks (``None`` for
    the tests' CPU runs)."""
    import jax

    devices = jax.devices()
    if not require_chip:
        return devices[:chips], None
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found "
                      f"{len(devices)}")
    return devices[:chips], peaks_for(devices[0].device_kind)


def peak_memory(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts backend compilations (persistent-cache loads included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


def run_closed(system, mix, tiles, seed, seconds, window):
    """Bag of tasks: ``backlog`` tiles outstanding, the next submitted as
    one completes.  The ramp is the warm-up: the window opens at the
    first completion and closes at the first completion at or after
    ``seconds`` later, so it holds whole rounds of the completions'
    pattern.  Returns (failed jobs and jobs done in the window, t_open,
    t_close)."""
    from bench import traffic
    from bench.system import make_job

    items = traffic.closed(mix, seed)
    for _ in range(int(mix["backlog"])):
        system.submit(make_job(next(items), tiles))
    seen = 0
    t_open = t_close = None
    while t_close is None:
        with system.done_event:
            if not system.done_event.wait_for(
                    lambda: len(system.done) > seen, timeout=1200.0):
                raise RuntimeError("no tile finished in 1200 s")
            new = system.done[seen:]
        seen += len(new)
        for job in new:
            if job.failed:
                continue
            if t_open is None:
                t_open = job.t_done
                window.open(t_open)
            elif job.t_done - t_open >= seconds:
                t_close = job.t_done
                break
        if t_close is None:
            for _ in new:
                system.submit(make_job(next(items), tiles))
    jobs = [j for j in system.done
            if j.failed or t_open < j.t_done <= t_close]
    return jobs, t_open, t_close


class Window:
    """The measured window: opens with the setup time, the program's
    counts and, traced, the profiler; closes by stopping the lanes."""

    def __init__(self, system, counter, capture):
        self.system, self.counter, self.capture = system, counter, capture

    def open(self, t_open: float) -> None:
        """Opens at ``t_open``; a trace starts now."""
        if self.capture is not None:
            self.capture.start()
        self.setup_s = t_open - T_START
        self.before = self.system.counts()
        self._c0 = self.counter.n

    def close(self):
        """Stop the lanes (each finishes its running op), then the
        trace; returns the trace or ``None``."""
        self.compiles = (self._c0, self.counter.n - self._c0)
        self.system.stop()
        self.t_end = time.monotonic()
        return self.capture.stop() if self.capture is not None else None


def sample(jobs, n, seed):
    done = [j for j in jobs if j.t_done is not None]
    rng = random.Random(seed * 1_000_003 + 41)
    return rng.sample(done, min(n, len(done)))


def measure(cell: str, seed: int, seconds: float, trace: bool,
            require_chip: bool = True, side: int | None = None,
            registry_hook=None, detail: dict | None = None) -> dict:
    """One run of ``cell``; returns the result object.  The keywords
    serve the tests, which run a cell at a small tile side on the CPU
    and break the timed path underneath, and the tool that calibrates
    the limits (``calibrate.py``): ``detail``, when given, receives the
    per-tile numbers and the window's timings."""
    from bench import compare, tiles as tile_pool

    spec, w, config, mix = spec_of(cell)
    side = side or int(config["tile_side"])
    lim = compare.limits(cell)
    cache = use_compile_cache()
    t0 = time.monotonic()
    tiles_ready = tile_pool.start_pool(int(mix["pool"]), side, seed)
    devices, peaks = find_chips(int(w["chips"]), require_chip)
    split = {"jax_init_s": time.monotonic() - t0}

    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from bench import trace as tr
    reference = importlib.import_module(f"bench.references.{config['reference']}")
    from bench.system import System

    counter = CompileCounter()
    t0 = time.monotonic()
    tiles, loaded = tiles_ready()
    # Beyond JAX's initialisation, which the generation overlaps.
    split["pool_loaded_s" if loaded else "pool_generated_s"] = (
        time.monotonic() - t0)

    t0 = time.monotonic()
    system = System(config)
    if registry_hook is not None:
        registry_hook(system.registry)
    split["build_s"] = time.monotonic() - t0

    window = Window(system, counter,
                    tr.Capture(BENCH / ".cache" / "trace") if trace else None)
    jobs, t_open, t_close = run_closed(system, mix, tiles, seed, seconds,
                                       window)
    trace_data = window.close()
    t_end = window.t_end
    memory_peak = peak_memory(devices)
    before, after = window.before, system.counts()
    setup_s = window.setup_s
    # The loop's ramp to its first completion, the cell's warm-up.
    split["ramp_s"] = setup_s - sum(split.values())
    compiles_setup, compiles_window = window.compiles

    completed = [j for j in jobs if j.t_done is not None]
    failed = len(jobs) - len(completed)
    checked = sample(jobs, int(lim["sample"]), seed)
    got = [system.outputs(j) for j in checked]
    payloads = [j.payload for j in checked]
    run = tr.RunData(
        cell=cell, config=config, mix=mix, side=side, seconds=seconds,
        t_open=t_open, t_close=t_close, t_end=t_end, jobs=jobs,
        before=before, after=after, memory_peak=memory_peak,
        peaks=peaks, trace=trace_data)
    del system, jobs, window
    gc.collect()

    t0 = time.monotonic()
    per_tile = [compare.tile_numbers(g, reference.run(p))
                for g, p in zip(got, payloads)]
    reference_s = time.monotonic() - t0
    numbers = compare.worst(per_tile)
    numbers["failed"] = failed
    numbers["host_fallbacks"] = after["host_fallbacks"] - before["host_fallbacks"]
    numbers["worker_errors"] = after["errors"]
    correct = bool(checked) and compare.judge(numbers, lim)

    e2e = {"setup_s": setup_s,
           "tiles_per_s": len(completed) / (t_close - t_open)}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(run.jobs),
              "failed": failed}
    if trace:
        metrics = {}
        for m in cell_metrics(spec, cell, "per_layer"):
            value = tr.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace_data.busy_s()
        device["window_s"] = trace_data.window_s()
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = trace_data.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(spec, cell, "end_to_end")}
        result["device"] = device
    result["checks"] = {k: {"value": numbers[k], "limit": lim[k]}
                        for k in compare.NUMBERS}

    split["setup_s"] = setup_s
    log(f"setup {json.dumps(split)} compile_cache={cache} "
        f"compiles_setup={compiles_setup} compiles_window={compiles_window}")
    latencies = sorted(j.latency for j in completed)
    log(f"window cell={cell} seed={seed} "
        f"tiles={len(run.jobs)} completed={len(completed)} failed={failed} "
        f"window_s={t_close - t_open:.6f} drain_s={t_end - t_close:.6f} "
        f"latency_min_max_s={latencies[:1] + latencies[-1:]} "
        f"memory_peak_bytes={memory_peak} e2e={json.dumps(e2e)}")
    log(f"done_at_s {[round(j.t_done - t_open, 3) for j in completed]}")
    log(f"variant_runs {json.dumps(tr.delta(before, after))}")
    log(f"reference tiles={len(checked)} reference_s={reference_s:.3f} "
        f"per_tile={json.dumps(per_tile)}")
    for k, v in result["checks"].items():
        log(f"check {k}={v['value']!r} limit={v['limit']!r}")
    if detail is not None:
        detail.update(per_tile=per_tile, numbers=numbers, e2e=e2e,
                      t_open=t_open, t_close=t_close, split=split)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result = measure(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except Refused as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
