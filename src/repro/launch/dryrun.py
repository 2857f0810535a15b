import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``
must succeed on the single-pod 16x16 mesh and the 2x16x16 multi-pod
mesh for every assigned architecture and input shape, with

* ``compiled.memory_analysis()``  -> bytes/device (fits 16 GB HBM?),
* ``compiled.cost_analysis()``    -> FLOPs / bytes for the roofline,
* collective bytes parsed from the optimized HLO (all-gather /
  all-reduce / reduce-scatter / all-to-all / collective-permute),

recorded per cell into ``--out`` (default ``dryrun_results.json`` in
the working directory).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --sweep [--multi-pod]
"""

import argparse
import json
import math
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, SHAPES, get_config, valid_cells
from ..models import build_model, make_plan
from ..optim import AdamW, AdamW8bit, OptState
from ..train import TrainState, make_prefill_step, make_serve_step, make_train_step
from ..models.attention import attention_options
from ..models.transformer import fsdp_gather
from .costs import cell_cost, hlo_cost_analysis
from .mesh import axes_for, make_production_mesh
from .sharding import (
    cache_specs,
    fsdp_gather_specs,
    input_structs,
    param_specs,
    to_shardings,
)

# TPU v5e hardware constants (roofline denominators).
PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link
HBM_BYTES = 16e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"%\S+\s*=\s*(\([^=]*?\)|[a-z0-9]+\[[0-9,]*\]\S*)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return m.group(1).count(",") + 1
    return 1


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Approximate per-device wire bytes of every collective op.

    Result shapes are parsed from each op's LHS (operands are printed
    as %refs in optimized HLO).  For all-reduce / all-to-all /
    collective-permute the result equals the operand; for all-gather
    the result is the full gathered tensor (~ring wire bytes); for
    reduce-scatter the *operand* is result x group_size, so we scale.
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "-done(" in line:
            continue
        shapes, kind = m.group(1), m.group(2)
        total = sum(
            _shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(shapes)
        )
        if kind == "reduce-scatter":
            total *= _group_size(line)
        out[kind] = out.get(kind, 0) + total
    return out


def _opt_shapes(param_shapes):
    f32 = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), t
    )
    return OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=f32(param_shapes),
        nu=f32(param_shapes),
    )


def _opt8_shapes(opt, param_shapes):
    return jax.eval_shape(opt.init, param_shapes)


def build_cell(arch: str, shape_name: str, mesh, opt8bit: bool = False,
               fsdp_mode: str = "naive"):
    """-> (fn, example args (ShapeDtypeStructs), in_shardings, donate)."""
    cfg = get_config(arch)
    ax = axes_for(mesh)
    tp = mesh.shape[ax.model]
    plan = make_plan(cfg, tp=tp, dp_axes=ax.data, tp_axis=ax.model)
    model = build_model(cfg, plan)
    shape = SHAPES[shape_name]

    pshapes = model.init_shapes()
    pspecs = param_specs(pshapes, cfg, ax, mesh)
    inputs, ispecs = input_structs(cfg, shape, ax, mesh)

    if shape.kind == "train":
        if opt8bit:
            opt = AdamW8bit(lr=3e-4)
            ostate = _opt8_shapes(opt, pshapes)
            # Row-wise codes keep the param's shape => reuse its spec;
            # scales keep only the leading-dim sharding.
            codes_specs = pspecs

            def sspec(spec_leaf):
                parts = list(spec_leaf) if len(spec_leaf) else []
                return P(*(parts[:1] + [None] * max(len(parts) - 1, 0)))

            scale_specs = jax.tree.map(
                sspec, pspecs, is_leaf=lambda x: isinstance(x, P)
            )
            from ..optim import Opt8State

            ospecs = Opt8State(
                step=P(), mu_q=codes_specs, mu_s=scale_specs,
                nu_q=codes_specs, nu_s=scale_specs,
            )
            state = TrainState(params=pshapes, opt=ostate)
            state_specs = TrainState(params=pspecs, opt=ospecs)
        else:
            opt = AdamW(lr=3e-4)
            state = TrainState(params=pshapes, opt=_opt_shapes(pshapes))
            state_specs = TrainState(
                params=pspecs,
                opt=OptState(step=P(), mu=pspecs, nu=pspecs),
            )
        gshard = (
            to_shardings(pspecs, mesh) if fsdp_mode == "gather" else None
        )
        step = make_train_step(model, opt, grad_shardings=gshard)
        args = (state, inputs)
        in_specs = (state_specs, ispecs)
        donate = (0,)
        return step, args, in_specs, donate, model, plan

    if shape.kind == "prefill":
        step = make_prefill_step(model, max_len=shape.seq_len)
        args = (pshapes, inputs)
        in_specs = (pspecs, ispecs)
        return step, args, in_specs, (), model, plan

    # decode / long-decode
    b = shape.global_batch
    cshapes = jax.eval_shape(
        lambda: model.init_caches(b, shape.seq_len)
    )
    cspecs = cache_specs(cshapes, cfg, ax, mesh, batch=b)
    step = make_serve_step(model)
    args = (pshapes, cshapes, inputs["tokens"], inputs["lengths"])
    in_specs = (pspecs, cspecs, ispecs["tokens"], ispecs["lengths"])
    donate = (1,)
    return step, args, in_specs, donate, model, plan


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             fsdp: str = "naive", causal_skip: bool = False,
             kv_quant: bool = False, opt8bit: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = math.prod(mesh.devices.shape)
    cfg = get_config(arch)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": n_chips,
        "fsdp": fsdp,
        "causal_skip": causal_skip,
        "kv_quant": kv_quant,
        "opt8bit": opt8bit,
    }
    t0 = time.perf_counter()
    with mesh, attention_options(causal_skip=causal_skip, kv_quant=kv_quant):
        step, args, in_specs, donate, model, plan = build_cell(
            arch, shape_name, mesh, opt8bit=opt8bit, fsdp_mode=fsdp
        )
        in_sh = to_shardings(in_specs, mesh)
        jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=donate)
        gather_map = None
        if fsdp == "gather" and SHAPES[shape_name].kind in ("train", "prefill"):
            ax = axes_for(mesh)
            gather_map = fsdp_gather_specs(
                model.init_shapes(), cfg, ax, mesh
            )
        with fsdp_gather(gather_map):
            lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = hlo_cost_analysis(compiled)
        hlo = compiled.as_text()

    coll = collective_bytes(hlo)
    coll_total = sum(coll.values())
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))

    # Roofline terms.  FLOPs/bytes come from the analytic cost model
    # (XLA CPU HloCostAnalysis counts while bodies once — see costs.py;
    # the raw HLO numbers are recorded as hlo_* for transparency).
    # Collective bytes come from the partitioned HLO (per-device shard
    # sizes): globalized x chips, the chips cancel in the term.
    cm = cell_cost(cfg, SHAPES[shape_name], tp=mesh.shape["model"],
                   causal_skip=causal_skip, kv_quant=kv_quant)
    rec.update(
        arch_name=cfg.name,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        flops_global=cm.flops,
        bytes_global=cm.bytes,
        flops_by=cm.flops_by,
        bytes_by=cm.bytes_by,
        hlo_flops_per_device=flops_dev,
        hlo_bytes_per_device=bytes_dev,
        coll_bytes_per_device=coll_total,
        coll_by_kind=coll,
        compute_term_s=cm.flops / (n_chips * PEAK_FLOPS),
        memory_term_s=cm.bytes / (n_chips * HBM_BW),
        collective_term_s=(coll_total * n_chips) / (n_chips * LINK_BW),
        q_waste=plan.attention.q_waste if plan.attention else 0.0,
        kv_overhead=plan.attention.kv_overhead if plan.attention else 1.0,
    )
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "peak_memory_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                rec[k] = int(v)
        args_b = rec.get("argument_size_in_bytes", 0)
        temp_b = rec.get("temp_size_in_bytes", 0)
        rec["fits_hbm"] = bool(args_b + temp_b < HBM_BYTES)
    dom = max(
        ("compute", "memory", "collective"),
        key=lambda k: rec[f"{k}_term_s" if k != "compute" else "compute_term_s"],
    )
    rec["dominant"] = dom
    # Useful-compute ratio: 6*N*D (or 6*N_active*D) vs compiled FLOPs.
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * cfg.active_params() * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * cfg.active_params() * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2 * cfg.active_params() * tokens
    rec["model_flops"] = float(model_flops)
    rec["useful_ratio"] = float(model_flops / cm.flops) if cm.flops else 0.0
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--fsdp", default="naive", choices=["naive", "gather"])
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--opt8bit", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args()

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results: list[dict] = []
    if out_path.exists():
        results = json.loads(out_path.read_text())

    def done(a, s, mp):
        mesh = "2x16x16" if mp else "16x16"
        return any(
            r["arch"] == a and r["shape"] == s and r["mesh"] == mesh
            and "error" not in r
            for r in results
        )

    cells: list[tuple[str, str, bool]] = []
    if args.sweep:
        for a in ARCH_IDS:
            for s in valid_cells(a):
                for mp in (False, True):
                    if not done(a, s, mp):
                        cells.append((a, s, mp))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, args.multi_pod)]

    for a, s, mp in cells:
        label = f"{a} x {s} x {'2x16x16' if mp else '16x16'}"
        print(f"=== {label}", flush=True)
        try:
            rec = run_cell(a, s, multi_pod=mp, fsdp=args.fsdp,
                           causal_skip=args.causal_skip,
                           kv_quant=args.kv_quant, opt8bit=args.opt8bit)
            print(
                f"    ok  compile={rec['compile_s']}s "
                f"flops={rec['flops_global']:.3e} "
                f"coll/dev={rec['coll_bytes_per_device']:.3e} "
                f"terms(c/m/coll)="
                f"{rec['compute_term_s']:.3f}/{rec['memory_term_s']:.3f}/"
                f"{rec['collective_term_s']:.3f}s "
                f"dominant={rec['dominant']} "
                f"useful={rec['useful_ratio']:.2f}",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            rec = {
                "arch": a, "shape": s,
                "mesh": "2x16x16" if mp else "16x16",
                "error": f"{type(e).__name__}: {e}",
            }
            print(f"    FAIL {rec['error'][:300]}", flush=True)
            traceback.print_exc()
        results = [
            r for r in results
            if not (r["arch"] == a and r["shape"] == s and r["mesh"] == rec["mesh"])
        ] + [rec]
        out_path.write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
