"""The system under test, built from a configuration file, and the
client that drives it.

A configuration fixes only what its deployment fixes: the workflow's
shape, the worker's lanes and options, and the variants registered.
Every other ``Manager`` and ``WorkerRuntime`` option stays at the
program's default, so a later change of a default shows in the cells.

The client stamps a tile done once it holds the sink outputs on the
host: the completion hook reads every ``feat_*`` table and the object
count of the tile's stages from the Manager.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from . import tiles as tile_pool
from .traffic import Item


@dataclass
class Job:
    """One tile of a run."""

    item: Item
    payload: np.ndarray
    t_submit: float = 0.0       # monotonic
    t_done: Optional[float] = None
    stage_uids: tuple = ()
    terminals: set = field(default_factory=set)
    failed: bool = False

    @property
    def latency(self) -> float:
        return np.inf if self.t_done is None else self.t_done - self.t_submit


class System:
    """One worker and one Manager in this process; the lanes are the
    runtime's threads."""

    def __init__(self, config: dict):
        from repro.app import build_workflow, register_variants
        from repro.core import (
            ConcreteWorkflow, LaneSpec, Manager, VariantRegistry,
            WorkerRuntime,
        )

        self.config = config
        self.registry = register_variants(VariantRegistry(),
                                          **config["variants"])
        self.workflow = build_workflow(**config["workflow"])
        self.cw = ConcreteWorkflow(self.workflow)
        self.mgr = Manager(self.cw)
        self.rt = WorkerRuntime(
            0, lanes=tuple(LaneSpec(k, i) for k, i in config["lanes"]),
            variant_registry=self.registry, **config.get("worker", {}),
        )
        self.rt.start()
        self.mgr.register_worker(self.rt)
        self._lock = threading.Lock()
        self._by_uid: dict[int, Job] = {}   # terminal stage uid -> job
        self.done: "list[Job]" = []
        self.done_event = threading.Condition(self._lock)
        self.mgr.open_stream()
        self.mgr.completion_hook = self._on_stage

    # -- submission ---------------------------------------------------------

    def submit(self, job: Job) -> None:
        from repro.core import DataChunk

        chunk = DataChunk(job.item.chunk_id, payload=job.payload)
        job.t_submit = time.monotonic()
        sis = self.cw.instantiate(chunk)
        uids = {si.uid for si in sis}
        job.stage_uids = tuple(sorted(uids))
        job.terminals = {si.uid for si in sis if not (si.dependents & uids)}
        with self._lock:
            for uid in job.terminals:
                self._by_uid[uid] = job
        self.mgr.submit_instances(sis)

    # -- completion ---------------------------------------------------------

    def _hold(self, uids) -> None:
        """The client's host read of a finished tile's outputs."""
        for uid in uids:
            for out in self.mgr.stage_outputs(uid).values():
                for k, v in (out or {}).items():
                    if k.startswith("feat_") or k == "n_objects":
                        np.asarray(v)

    def _on_stage(self, uid: int) -> None:
        with self._lock:
            job = self._by_uid.pop(uid, None)
            if job is None:
                return
            job.terminals.discard(uid)
            if job.terminals:
                return
        self._hold(job.stage_uids)
        self._finish(job)

    def _finish(self, job: Job, failed: bool = False) -> None:
        now = time.monotonic()
        with self._lock:
            if job.t_done is not None or job.failed:
                return
            job.failed = failed
            if not failed:
                job.t_done = now
            self.done.append(job)
            self.done_event.notify_all()

    # -- outputs and accounting ----------------------------------------------

    def outputs(self, job: Job) -> dict:
        """Everything the program produced for one tile, merged into one
        state dict (sink outputs carry their upstream state)."""
        merged: dict = {}
        for uid in job.stage_uids:
            for out in self.mgr.stage_outputs(uid).values():
                merged.update(out or {})
        return merged

    def counts(self) -> dict:
        st = self.rt.stats()
        return {"variant_runs": dict(st["variant_runs"]),
                "host_fallbacks": int(st["host_fallbacks"]),
                "errors": len(self.rt.errors)}

    def stop(self) -> None:
        """Stop the lanes, waiting for each one's running op to end."""
        self.rt.stop()
        for t in threading.enumerate():
            if t.name.startswith(f"worker{self.rt.worker_id}-"):
                t.join(timeout=300.0)
        self.mgr.close_stream(timeout=0.0)


def make_job(item: Item, tiles: np.ndarray) -> Job:
    return Job(item=item,
               payload=tile_pool.transform(tiles[item.pool_index],
                                           item.transform))
