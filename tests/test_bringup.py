"""What running on the chip needs, checked on the CPU.

``chip_smoke``'s phases at 128² (Pallas kernels in interpret mode),
its refusal to run without a TPU, paper-scale tile generation, the
compile-cache location, lane-to-device binding on virtual devices, and
the worker's honest host tier and fallback accounting.
"""

import os
import shutil
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.app import synth_tile
from repro.app.segmentation import MAX_OBJECTS
from repro.core import (
    AbstractWorkflow,
    ConcreteWorkflow,
    DataChunk,
    LaneSpec,
    Manager,
    ManagerConfig,
    Operation,
    Stage,
    VariantRegistry,
    WorkerRuntime,
)
from repro.staging import op_key

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_chip_smoke_phases_on_cpu():
    """Phases A, B and C at 128², the ``tpu`` lanes on the CPU backend
    (Pallas kernels interpreted): every check passes."""
    sizes = chip_smoke.Sizes(batch_side=128, batch_tiles=3, ref_side=128,
                             ref_tiles=1, serve_requests=2)
    assert chip_smoke.run_phases(sizes, seed=0) == []


def test_chip_smoke_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_synth_tile_paper_scale():
    """A 4096² tile takes seconds; its nuclei keep the 256² pixel size
    (areas inside area_threshold's [24, 8192]) and their count grows
    with the tile's area, under MAX_OBJECTS."""
    t0 = time.perf_counter()
    tile, truth = synth_tile(0, size=4096, seed=0, with_truth=True)
    assert time.perf_counter() - t0 < 60.0
    assert tile.shape == (4096, 4096, 3) and tile.dtype == np.uint8
    assert 6 * 2 * 256 <= truth.n_nuclei < MAX_OBJECTS
    assert 24 <= min(truth.nuclei_areas) and max(truth.nuclei_areas) <= 8192
    assert truth.nuclei_mask.sum() <= sum(truth.nuclei_areas)


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    from repro.compile_cache import CACHE_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    try:
        got = enable_compile_cache()
        if env_dir is None:
            assert got == str(CACHE_DIR) == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            # JAX reads the variable itself; nothing is set.
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


LANES_SCRIPT = textwrap.dedent(
    """
    import threading, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (AbstractWorkflow, ConcreteWorkflow, DataChunk,
        LaneSpec, Manager, ManagerConfig, Operation, Stage, VariantRegistry,
        WorkerRuntime)
    from repro.core.worker import _place

    devices = jax.devices()
    assert len(devices) == 4, devices
    seen = []
    lock = threading.Lock()

    def note(x):
        lane = int(threading.current_thread().name.rsplit("tpu", 1)[1])
        with lock:
            seen.append((lane, x.devices()))

    def make(ctx):
        time.sleep(0.01)
        x = jnp.full((8, 128), float(ctx.chunk.chunk_id))
        note(x)
        return x

    def bump(ctx):
        x = ctx.sole_input()
        y = jnp.asarray(x) + 1.0
        note(y)
        return y

    reg = VariantRegistry()
    reg.register("make", "tpu", make)
    reg.register("bump", "tpu", bump)
    wf = AbstractWorkflow.chain("w", [
        Stage.single(Operation("make")), Stage.single(Operation("bump"))])
    cw = ConcreteWorkflow.replicate(wf, [DataChunk(i) for i in range(24)])
    rt = WorkerRuntime(0, lanes=tuple(LaneSpec("tpu", i) for i in range(4)),
                       variant_registry=reg)
    mgr = Manager(cw, ManagerConfig(window=8, backup_tasks=False))
    rt.start()
    mgr.register_worker(rt)
    assert mgr.run(timeout=120.0)
    rt.stop()
    assert not rt.errors, rt.errors
    assert {lane for lane, _ in seen} == {0, 1, 2, 3}, seen
    assert all(devs == {devices[lane]} for lane, devs in seen), seen
    for si in cw.stage_instances.values():
        if si.stage.name == "bump":
            out = mgr.stage_outputs(si.uid)["bump"]
            assert isinstance(out, np.ndarray)  # downloaded at commit
            assert out[0, 0] == si.chunk.chunk_id + 1.0
    # Upload: a device array from another chip moves to the lane's chip;
    # host arrays are left for the op to upload.
    host = np.ones(3)
    moved = _place({"a": jax.device_put(jnp.ones(3), devices[3]), "b": host},
                   devices[1])
    assert moved["a"].devices() == {devices[1]} and moved["b"] is host
    print("LANES_OK")
    """
)


def test_lanes_bind_to_their_devices():
    """Accelerator lane i runs on jax.devices()[i] (4 virtual CPU devices)."""
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": str(ROOT / "src"),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    out = subprocess.run(
        [sys.executable, "-c", LANES_SCRIPT], capture_output=True,
        text=True, timeout=300, env=env,
    )
    assert "LANES_OK" in out.stdout, out.stderr[-3000:]


def test_lane_index_beyond_devices_is_refused():
    rt = WorkerRuntime(0, lanes=(LaneSpec("tpu", len(jax.devices())),))
    with pytest.raises(ValueError, match="needs device"):
        rt.start()


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None),
])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """Pallas wrappers interpret on CPU, compile on TPU, and refuse any
    other backend rather than interpreting there."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    ops._interpret.cache_clear()
    try:
        if interpret is None:
            with pytest.raises(RuntimeError, match="'gpu' backend"):
                ops._interpret()
        else:
            assert ops._interpret() is interpret
    finally:
        ops._interpret.cache_clear()


def _one_op_workflow(n: int):
    wf = AbstractWorkflow.chain("w", [Stage.single(Operation("op"))])
    return ConcreteWorkflow.replicate(wf, [DataChunk(i) for i in range(n)])


def test_accelerator_lane_host_fallback_is_counted():
    reg = VariantRegistry()
    reg.register("op", "cpu", lambda ctx: ctx.chunk.chunk_id)
    cw = _one_op_workflow(3)
    rt = WorkerRuntime(0, lanes=(LaneSpec("gpu", 0),), variant_registry=reg)
    mgr = Manager(cw, ManagerConfig(backup_tasks=False))
    rt.start()
    try:
        mgr.register_worker(rt)
        assert mgr.run(timeout=60.0)
    finally:
        rt.stop()
    st = rt.stats()
    assert st["host_fallbacks"] == 3
    assert st["variant_runs"] == {"op/cpu": 3}
    assert rt.metrics.snapshot()["worker.host_fallbacks"] == 3


def test_accelerator_outputs_reach_the_host_tier_as_host_arrays():
    """The host write-back is a real download: neither the worker's
    host tier nor the Manager's outputs pin device buffers."""
    reg = VariantRegistry()
    reg.register("op", "gpu",
                 lambda ctx: {"x": jnp.full((4,), float(ctx.chunk.chunk_id)),
                              "n": ctx.chunk.chunk_id})
    cw = _one_op_workflow(2)
    rt = WorkerRuntime(0, lanes=(LaneSpec("gpu", 0),), variant_registry=reg)
    mgr = Manager(cw, ManagerConfig(backup_tasks=False))
    rt.start()
    try:
        mgr.register_worker(rt)
        assert mgr.run(timeout=60.0)
    finally:
        rt.stop()
    for si in cw.stage_instances.values():
        out = mgr.stage_outputs(si.uid)["op"]
        assert isinstance(out["x"], np.ndarray) and out["n"] == si.chunk.chunk_id
        held = rt.store.get(op_key(si.op_instances[0].uid))
        assert isinstance(held["x"], np.ndarray)
    assert rt.stats()["variant_runs"] == {"op/gpu": 2}
    assert rt.stats()["host_fallbacks"] == 0


def test_spawn_refuses_accelerator_workers_beyond_the_chips(monkeypatch):
    from repro.transport import endpoint as E

    spec = E.WorkerSpec(worker_id=0, registry="repro.transport.demo:x",
                        lanes=(("tpu", 0),))
    monkeypatch.setattr(E, "host_chips", lambda: 0)
    with pytest.raises(RuntimeError, match="0 chip"):
        E.spawn_worker("localhost:1", spec)

    class Alive:
        def is_alive(self):
            return True

    monkeypatch.setattr(E, "host_chips", lambda: 1)
    monkeypatch.setattr(E, "_accel_children", [Alive()])
    with pytest.raises(RuntimeError, match="already hold them"):
        E.spawn_worker("localhost:1", spec)
