#!/usr/bin/env python3
"""Bring-up check: the WSI pipeline runs end to end on a TPU.

    python chip_smoke.py [--seed N]     # one chip: phases A, B and C
    python chip_smoke.py --chips 4      # four chips: the multi-lane phase

Everything goes through the normal path — ``Manager`` -> ``WorkerRuntime``
lanes -> function variants, and ``RequestGateway`` in front for serving —
in this one process, which is the only one that touches the chip.

* **A, batch.** Generated 4096² tiles through one worker with a single
  ``tpu`` lane and the fused workflow, so the Pallas ``recon_to_nuclei``,
  ``fill_holes`` and ``feature_fused`` variants run.  Prints each tile's
  object count and the device's memory after it, which must level off:
  more tiles than the chip could hold at once pass through.
* **B, reference.** 1024² tiles through the same lane, unfused (the
  Pallas ``color_deconv`` variant) and fused, each compared with the
  NumPy CPU variants (``run_tile(tile, "cpu")``).
* **C, serving.** 1024² requests through a ``RequestGateway`` over one
  ``tpu`` and two ``cpu`` lanes; every request must be answered.
* **--chips 4.** Phase A's tiles, fewer of them, on one worker with four
  ``tpu`` lanes (lane *i* drives chip *i*), against the same tiles on
  one lane.

Tiles come from ``--seed``.  Any failed or quarantined stage, worker
error, mismatch, capped object count, or host implementation run on a
``tpu`` lane exits non-zero.  On success the last line of standard
output is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero before any phase and prints no such line.
"""

from __future__ import annotations

import argparse
import functools
import json
import multiprocessing as mp
import os
import sys
import threading
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LANE = "tpu"

#: The Pallas kernel variant each of these ops binds on ``tpu`` lanes.
PALLAS = {
    "recon_to_nuclei": "recon_pallas",
    "fill_holes": "fill_holes_pallas",
    "feature_fused": "feature_fused_pallas",
    "color_deconv": "color_deconv_pallas",
}

#: Segmentation masks: share of pixels that must agree with NumPy.
MASK_AGREEMENT = 0.999
#: (rtol, atol) per output against the NumPy variants.  A CPU rehearsal
#: of the accelerator path met these with 30x margin; the margin covers
#: the TPU's last-bit differences in log10/sqrt and f32 reductions.
TOLERANCES = {
    "gray": (1e-5, 1e-3),
    "recon": (1e-5, 1e-3),
    "dist": (0.0, 0.0),
    "hema": (1e-4, 1e-4),
    "eosin": (1e-4, 1e-4),
    "feat_pixel": (2e-3, 1e-4),
    "feat_gradient": (1e-3, 1e-3),
    "feat_haralick": (1e-4, 1e-5),
    "feat_canny": (0.0, 1e-2),
    "feat_morph": (1e-5, 1e-4),
}
MASKS = ("fg", "rbc", "fg_open", "nuclei", "mask_at", "mask", "markers")


@dataclass(frozen=True)
class Sizes:
    batch_side: int = 4096
    batch_tiles: int = 32
    ref_side: int = 1024
    ref_tiles: int = 2
    serve_requests: int = 4


def log(*parts) -> None:
    print(*parts, flush=True)


def make_tiles(n: int, side: int, seed: int, first_id: int = 0) -> list:
    """Tiles ``first_id ..`` from ``seed``, generated on host processes
    that never touch JAX (the generator is NumPy and holds the GIL)."""
    from repro.app.tiles import synth_tile

    make = functools.partial(synth_tile, size=side, seed=seed)
    ids = range(first_id, first_id + n)
    if side < 2048:  # a pool would cost more than it saves
        return [make(i) for i in ids]
    workers = max(1, min(n, len(os.sched_getaffinity(0)) - 1, 8))
    with ProcessPoolExecutor(workers, mp_context=mp.get_context("spawn")) as ex:
        return list(ex.map(make, ids))


def make_registry():
    from repro.app import register_variants
    from repro.core import VariantRegistry

    return register_variants(VariantRegistry(), accel_kind=LANE,
                             with_pallas=True)


def device_memory(device) -> tuple:
    stats = device.memory_stats()
    if not stats:
        return None, None
    return stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")


class Run:
    """One workflow run through a Manager over one fresh worker."""

    def __init__(self, tiles, *, lanes, fused, reg, failures, name,
                 on_feature_done=None, window=4):
        from repro.app import build_workflow
        from repro.core import (
            ConcreteWorkflow, DataChunk, LaneSpec, Manager, ManagerConfig,
            WorkerRuntime,
        )

        cw = ConcreteWorkflow.replicate(
            build_workflow(fused=fused),
            [DataChunk(i, payload=t) for i, t in enumerate(tiles)],
        )
        self.rt = WorkerRuntime(
            0, lanes=tuple(LaneSpec(k, i) for k, i in lanes),
            variant_registry=reg,
        )
        mgr = Manager(cw, ManagerConfig(
            window=window, heartbeat_timeout=600.0, backup_tasks=False,
        ))
        by_uid = {si.uid: si for si in cw.stage_instances.values()}
        if on_feature_done is not None:
            mgr.completion_hook = lambda uid: (
                by_uid[uid].stage.name == "features"
                and on_feature_done(by_uid[uid].chunk.chunk_id)
            )
        self.rt.start()
        try:
            mgr.register_worker(self.rt)
            ok = mgr.run(timeout=1800.0)
        finally:
            self.rt.stop()
        st = mgr.stats()
        self.stats = self.rt.stats()
        if not ok:
            failures.append(f"{name}: the workflow did not complete")
        if st["stage_failures"] or st["quarantined"] or self.rt.errors:
            failures.append(
                f"{name}: {st['stage_failures']} failed and "
                f"{st['quarantined']} quarantined stages, worker errors "
                f"{[f'{type(e).__name__}: {e}' for _, e in self.rt.errors]}"
            )
        # tile -> {"seg": bwlabel state, "feat": {op: output}}
        self.tiles: dict[int, dict] = {}
        for si in cw.stage_instances.values():
            out = mgr.stage_outputs(si.uid)
            t = self.tiles.setdefault(si.chunk.chunk_id, {})
            if si.stage.name == "segmentation":
                t["seg"] = out.get("bwlabel")
            else:
                t["feat"] = out
        # A time ends with a host read of every tile's feature outputs.
        for t in self.tiles.values():
            for out in (t.get("feat") or {}).values():
                for k, v in (out or {}).items():
                    if k.startswith("feat_"):
                        np.asarray(v)

    def check_variants(self, reg, failures, name, lane_kinds=(LANE,)):
        """Print which implementation ran each op; fail on host fallbacks
        on an accelerator lane."""
        runs = self.stats["variant_runs"]
        for key in sorted(runs):
            op, kind = key.split("/")
            impl = reg.get(op).implementation(kind).__name__
            log(f"{name} op={op} kind={kind} impl={impl} runs={runs[key]}")
            if kind not in lane_kinds:
                failures.append(f"{name}: {op} ran a {kind} implementation")
        if self.stats["host_fallbacks"]:
            failures.append(
                f"{name}: {self.stats['host_fallbacks']} ops ran a host "
                f"implementation on a {LANE} lane"
            )
        return runs

    def n_objects(self, tile_id) -> int:
        seg = self.tiles.get(tile_id, {}).get("seg") or {}
        return int(seg.get("n_objects", -1))


def pallas_ran(reg, runs: dict, op: str) -> bool:
    return (bool(runs.get(f"{op}/{LANE}"))
            and reg.get(op).implementation(LANE).__name__ == PALLAS[op])


def object_count_ok(n: int) -> bool:
    from repro.app.segmentation import MAX_OBJECTS

    return 0 < n < MAX_OBJECTS


def compare(ref: dict, run: Run, tile_id: int) -> list[str]:
    """Mismatches of one run's tile against the NumPy reference state."""
    t = run.tiles.get(tile_id, {})
    seg = t.get("seg") or {}
    got: dict = dict(seg)
    for out in (t.get("feat") or {}).values():
        got.update({k: v for k, v in (out or {}).items()
                    if k.startswith("feat_") or k in ("hema", "eosin")})
    bad = []
    if got.get("n_objects") != ref["n_objects"]:
        bad.append(f"n_objects {got.get('n_objects')} != {ref['n_objects']}")
    planes = {k: (ref[k], got.get(k)) for k in MASKS}
    planes["labels>0"] = (ref["labels"] > 0, np.asarray(got["labels"]) > 0)
    planes["objects"] = (ref["objects"], got.get("objects"))
    for k, (want, have) in planes.items():
        agree = float((np.asarray(want) == np.asarray(have)).mean())
        if agree < MASK_AGREEMENT:
            bad.append(f"{k} agrees on {agree:.6f} of pixels")
    for k, (rtol, atol) in TOLERANCES.items():
        want = np.asarray(ref[k], np.float64)
        have = np.asarray(got[k], np.float64)
        close = np.isclose(have, want, rtol=rtol, atol=atol)
        if k.startswith("feat_") and not close.all():
            bad.append(f"{k}: {int((~close).sum())} values outside "
                       f"rtol={rtol} atol={atol}, max |diff| "
                       f"{np.abs(have - want).max():.3g}")
        elif not k.startswith("feat_") and close.mean() < MASK_AGREEMENT:
            bad.append(f"{k}: {close.mean():.6f} of pixels within "
                       f"rtol={rtol} atol={atol}")
    return bad


def phase_batch(sizes: Sizes, seed: int, device, failures) -> None:
    t0 = time.perf_counter()
    tiles = make_tiles(sizes.batch_tiles, sizes.batch_side, seed)
    log(f"A setup tiles={len(tiles)} side={sizes.batch_side} "
        f"host_cpus={len(os.sched_getaffinity(0))} "
        f"generate_s={time.perf_counter() - t0:.3f}")
    reg = make_registry()
    memory: list[tuple] = []  # (tile, bytes_in_use, peak) per completion
    lock = threading.Lock()

    def on_done(tile_id):
        with lock:
            memory.append((tile_id, *device_memory(device)))

    t0 = time.perf_counter()
    run = Run(tiles, lanes=[(LANE, 0)], fused=True, reg=reg,
              failures=failures, name="A", on_feature_done=on_done)
    wall = time.perf_counter() - t0
    for k, (tid, in_use, peak) in enumerate(memory, 1):
        n = run.n_objects(tid)
        log(f"A tile={tid} done={k} n_objects={n} "
            f"bytes_in_use={in_use} peak_bytes_in_use={peak}")
    counts = [run.n_objects(i) for i in range(len(tiles))]
    good = sum(object_count_ok(n) for n in counts)
    if good != len(tiles):
        failures.append(f"A: {len(tiles) - good} tiles with n_objects "
                        f"outside (0, MAX_OBJECTS): {counts}")
    runs = run.check_variants(reg, failures, "A")
    for op in ("recon_to_nuclei", "fill_holes", "feature_fused"):
        if not pallas_ran(reg, runs, op):
            failures.append(f"A: the Pallas {op} variant never ran")
    peaks = [p for _, _, p in memory if p is not None]
    if len(peaks) == len(tiles):
        half, last = peaks[len(peaks) // 2 - 1], peaks[-1]
        state = run.tiles[0]["feat"]["feature_fused"]
        tile_bytes = sum(
            np.asarray(v).nbytes for v in state.values() if hasattr(v, "shape")
        )
        log(f"A peak_bytes_in_use half={half} last={last} "
            f"tile_state_bytes={tile_bytes}")
        if last - half >= tile_bytes:
            failures.append(f"A: peak device memory grew by {last - half} "
                            f"bytes over the second half of the run")
    log(f"A wall_s={wall:.3f} tiles={len(tiles)} "
        f"tiles_per_s={len(tiles) / wall:.4f}")


def phase_reference(sizes: Sizes, seed: int, refs, tiles, failures) -> None:
    reg = make_registry()
    for fused in (False, True):
        name = f"B-{'fused' if fused else 'unfused'}"
        t0 = time.perf_counter()
        run = Run(tiles, lanes=[(LANE, 0)], fused=fused, reg=reg,
                  failures=failures, name=name)
        wall = time.perf_counter() - t0
        runs = run.check_variants(reg, failures, name)
        pallas = "feature_fused" if fused else "color_deconv"
        if not pallas_ran(reg, runs, pallas):
            failures.append(f"{name}: the Pallas {pallas} variant never ran")
        for i, ref in enumerate(refs):
            bad = compare(ref.result(), run, i)
            log(f"{name} tile={i} n_objects={run.n_objects(i)} "
                f"reference_n_objects={ref.result()['n_objects']} "
                f"match={not bad}")
            failures.extend(f"{name} tile {i}: {b}" for b in bad)
        log(f"{name} wall_s={wall:.3f} tiles={len(tiles)}")


def phase_serving(sizes: Sizes, seed: int, tiles, failures) -> None:
    from repro.app import build_workflow
    from repro.core import (
        ConcreteWorkflow, DataChunk, LaneSpec, Manager, ManagerConfig,
        WorkerRuntime,
    )
    from repro.serving import GatewayConfig, RequestGateway

    reg = make_registry()
    cw = ConcreteWorkflow(build_workflow(fused=True))
    mgr = Manager(cw, ManagerConfig(window=4, heartbeat_timeout=600.0,
                                    backup_tasks=False))
    rt = WorkerRuntime(
        0, lanes=(LaneSpec(LANE, 0), LaneSpec("cpu", 0), LaneSpec("cpu", 1)),
        policy="pats", variant_registry=reg,
    )
    rt.start()
    t0 = time.perf_counter()
    try:
        mgr.register_worker(rt)
        gw = RequestGateway(mgr, GatewayConfig(max_inflight=4),
                            tenants={"viewer": 1.0})
        reqs = [gw.submit("viewer", DataChunk(i, payload=tiles[i % len(tiles)]))
                for i in range(sizes.serve_requests)]
        closed = gw.close(timeout=1800.0)
    finally:
        rt.stop()
    wall = time.perf_counter() - t0
    st = gw.stats
    lat = ",".join(f"{r.latency:.3f}" for r in reqs if r.latency is not None)
    log(f"C requests={len(reqs)} completed={st.completed} shed={st.shed} "
        f"failed={st.failed} latencies_s={lat} wall_s={wall:.3f}")
    stats = rt.stats()
    log(f"C variant_runs={stats['variant_runs']} "
        f"host_fallbacks={stats['host_fallbacks']}")
    if not closed or st.completed != len(reqs) or st.shed or st.failed:
        failures.append(f"C: {st.completed}/{len(reqs)} requests answered, "
                        f"{st.shed} shed, {st.failed} failed")
    if rt.errors or stats["host_fallbacks"]:
        failures.append(f"C: worker errors {rt.errors}, host fallbacks "
                        f"{stats['host_fallbacks']}")


def run_phases(sizes: Sizes, seed: int) -> list[str]:
    """Phases A, B and C on the default device; returns the failures."""
    import jax

    from repro.app import run_tile

    device = jax.devices()[0]
    failures: list[str] = []
    ref_tiles = make_tiles(sizes.ref_tiles, sizes.ref_side, seed,
                           first_id=10_000)
    # The NumPy references run on host threads while phase A holds the
    # chip.
    with ThreadPoolExecutor(sizes.ref_tiles) as pool:
        t0 = time.perf_counter()
        refs = [pool.submit(run_tile, t, "cpu") for t in ref_tiles]
        phase_batch(sizes, seed, device, failures)
        for ref in refs:
            ref.result()
        log(f"B reference_ready_s={time.perf_counter() - t0:.3f}")
        phase_reference(sizes, seed, refs, ref_tiles, failures)
    phase_serving(sizes, seed, ref_tiles, failures)
    return failures


def run_four_chips(sizes: Sizes, seed: int, chips: int) -> list[str]:
    """Phase A's tiles on ``chips`` lanes of one worker vs on one lane."""
    import jax

    failures: list[str] = []
    tiles = make_tiles(sizes.batch_tiles, sizes.batch_side, seed)
    reg = make_registry()
    produced: list[tuple[str, set]] = []  # (lane thread, output devices)
    lock = threading.Lock()
    for op in reg.names():
        var = reg.get(op)
        fn = var.impls[LANE]

        def recording(ctx, _fn=fn):
            out = _fn(ctx)
            devs = {d for x in jax.tree_util.tree_leaves(out)
                    if isinstance(x, jax.Array) for d in x.devices()}
            with lock:
                produced.append((threading.current_thread().name, devs))
            return out

        recording.__name__ = fn.__name__
        var.impls[LANE] = recording
    runs = {}
    for n in (1, chips):
        produced.clear()
        t0 = time.perf_counter()
        runs[n] = Run(tiles, lanes=[(LANE, i) for i in range(n)], fused=True,
                      reg=reg, failures=failures, name=f"lanes{n}",
                      window=2 * n)
        log(f"lanes{n} wall_s={time.perf_counter() - t0:.3f} "
            f"tiles={len(tiles)} lane_busy={runs[n].stats['lane_busy']}")
        runs[n].check_variants(reg, failures, f"lanes{n}")
    one, many = runs[1], runs[chips]
    for i in range(len(tiles)):
        n1, n4 = one.n_objects(i), many.n_objects(i)
        ref = dict(one.tiles[i]["seg"])
        for out in one.tiles[i]["feat"].values():
            ref.update(out)
        bad = compare(ref, many, i)
        log(f"tile={i} n_objects_1={n1} n_objects_{chips}={n4} "
            f"match={not bad}")
        failures.extend(f"tile {i}: {b}" for b in bad)
        if not object_count_ok(n1):
            failures.append(f"tile {i}: n_objects={n1}")
    devices = jax.devices()
    per_lane: dict[int, set] = {}  # lane index -> devices of its outputs
    for thread, devs in produced:  # lane threads: worker<id>-<kind><index>
        per_lane.setdefault(int(thread.rsplit(LANE, 1)[1]), set()).update(devs)
    shown = {i: sorted(map(str, d)) for i, d in sorted(per_lane.items())}
    log(f"lanes{chips} output devices per lane: {shown}")
    for i in range(chips):
        if not many.stats["lane_busy"].get(f"{LANE}{i}"):
            failures.append(f"lane {LANE}{i} executed no op")
        if per_lane.get(i, set()) - {devices[i]}:
            failures.append(f"lane {LANE}{i} produced arrays on "
                            f"{per_lane[i] - {devices[i]}}")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device kind={devices[0].device_kind} count={len(devices)} "
        f"compile_cache={cache} seed={args.seed}")
    t0 = time.perf_counter()
    if args.chips == 1:
        failures = run_phases(Sizes(), args.seed)
    else:
        failures = run_four_chips(Sizes(batch_tiles=8), args.seed, args.chips)
    log(f"total_wall_s={time.perf_counter() - t0:.3f}")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
