"""Sharding rules engine: parameter/activation PartitionSpecs per arch.

Rules are path-based (MaxText-style logical axes) with divisibility
guards — a dimension is only sharded if the mesh axis divides it, so
every arch in the zoo lowers on the fixed production mesh.  Parameters
are 2-D sharded (TP over ``model``, FSDP over ``data``) which also
ZeRO-shards the Adam state for free (the optimizer state mirrors the
parameter tree and reuses these specs).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ShapeSpec
from ..models.config import ArchConfig
from .mesh import MeshAxes, auto_axes

__all__ = [
    "param_specs",
    "input_structs",
    "cache_specs",
    "to_shardings",
]


def _div(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _leaf_spec(path: str, shape: tuple[int, ...], cfg: ArchConfig,
               ax: MeshAxes, dsz: int, msz: int) -> P:
    """Spec for an unstacked leaf (no leading layer dim)."""
    nd = len(shape)
    data = ax.data if len(ax.data) > 1 else ax.data[0]

    def dspec(i):  # shard dim i over data axes if divisible
        return data if _div(shape[i], dsz) else None

    def mspec(i):
        return ax.model if _div(shape[i], msz) else None

    if nd == 0 or max(shape) < 128:
        return P()
    if "embed" in path and nd == 2:                 # (V, D)
        return P(mspec(0), dspec(1))
    if path.endswith("head") and nd == 2:           # (D, V)
        return P(dspec(0), mspec(1))
    if "attn/" in path or "xattn/" in path:
        if path.endswith("wq") and nd == 4:         # (D, slots, g, hd)
            return P(dspec(0), mspec(1), None, None)
        if path.endswith(("wk", "wv")) and nd == 3:  # (D, slots, hd)
            return P(dspec(0), mspec(1), None)
        if path.endswith("wo") and nd == 4:         # (slots, g, hd, D)
            return P(mspec(0), None, None, dspec(3))
        if path.endswith("bq") and nd == 3:
            return P(mspec(0), None, None)
        if path.endswith(("bk", "bv")) and nd == 2:
            return P(mspec(0), None)
        return P()                                  # head_mask etc.
    if "moe/" in path:
        if path.endswith("router") and nd == 2:     # (D, E)
            return P(dspec(0), mspec(1))
        if nd == 3 and path.endswith(("w_up", "w_gate")):  # (E, D, F)
            return P(mspec(0), dspec(1), None)
        if nd == 3 and path.endswith("w_down"):     # (E, F, D)
            return P(mspec(0), None, dspec(2))
        return P()
    if path.endswith(("w_up", "w_gate")) and nd == 2:   # (D, F)
        return P(dspec(0), mspec(1))
    if path.endswith("w_down") and nd == 2:             # (F, D)
        return P(mspec(0), dspec(1))
    if "mamba/" in path:
        if path.endswith("in_proj"):                # (D, d_in_proj)
            return P(dspec(0), None)
        if path.endswith("out_proj"):               # (d_inner, D)
            return P(None, dspec(1))
        return P()
    if "cell/" in path:                             # xlstm cells
        if nd >= 2 and _div(shape[0], dsz) and shape[0] >= 256:
            return P(data, *([None] * (nd - 1)))
        return P()
    if nd == 2 and _div(shape[0], dsz) and shape[0] >= 1024:
        return P(data, None)                        # generic large matrix
    return P()


_STACKED_PREFIXES = ("blocks", "enc_blocks", "xl_blocks")


def param_specs(param_shapes: Any, cfg: ArchConfig, ax: MeshAxes,
                mesh) -> Any:
    dsz = ax.data_size(mesh)
    msz = ax.model_size(mesh)

    def rule(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        stacked = pstr.startswith(("blocks", "enc_blocks")) or "/blocks/" in pstr
        if stacked:
            inner = _leaf_spec(pstr, shape[1:], cfg, ax, dsz, msz)
            return P(None, *inner)
        return _leaf_spec(pstr, shape, cfg, ax, dsz, msz)

    return jax.tree_util.tree_map_with_path(rule, param_shapes)


# --------------------------------------------------------------------------
# Inputs (ShapeDtypeStructs + specs) per (arch, shape)
# --------------------------------------------------------------------------


def _batch_spec(batch: int, ax: MeshAxes, mesh) -> Any:
    data = ax.data if len(ax.data) > 1 else ax.data[0]
    return data if _div(batch, ax.data_size(mesh)) else None


def input_structs(cfg: ArchConfig, shape: ShapeSpec, ax: MeshAxes, mesh):
    """-> (inputs pytree of ShapeDtypeStruct, matching PartitionSpecs)."""
    b, s = shape.global_batch, shape.seq_len
    bspec = _batch_spec(b, ax, mesh)
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    structs: dict[str, Any] = {}
    specs: dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        if cfg.family == "audio":
            structs["tokens"] = tok
            specs["tokens"] = P(bspec, None)
            structs["embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_frames, cfg.d_model), jnp.float32
            )
            specs["embeds"] = P(bspec, None, None)
        elif cfg.frontend == "vision_stub":
            structs["embeds"] = jax.ShapeDtypeStruct(
                (b, s, cfg.d_model), jnp.float32
            )
            specs["embeds"] = P(bspec, None, None)
            if shape.kind == "prefill":
                structs["tokens"] = tok
                specs["tokens"] = P(bspec, None)
        else:
            structs["tokens"] = tok
            specs["tokens"] = P(bspec, None)
    else:  # decode shapes: one new token + lengths
        structs["tokens"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        specs["tokens"] = P(bspec)
        structs["lengths"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        specs["lengths"] = P(bspec)
    return structs, specs


def cache_specs(cache_shapes: Any, cfg: ArchConfig, ax: MeshAxes, mesh,
                *, batch: int) -> Any:
    """Specs for decode caches.

    KV caches (L, B, slots, Smax, hd): batch over data when divisible,
    slots over model; for batch=1 long-context, the cache *sequence*
    dim shards over data (sequence parallelism) instead.
    """
    dsz = ax.data_size(mesh)
    msz = ax.model_size(mesh)
    data = ax.data if len(ax.data) > 1 else ax.data[0]
    long_ctx = not _div(batch, dsz)

    def rule(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        if pstr.startswith(("kv", "shared_kv")) and len(shape) == 5:
            # (L, B, slots, Smax, hd)
            mdim = ax.model if _div(shape[2], msz) else None
            if long_ctx:
                sdim = data if _div(shape[3], dsz) else None
                return P(None, None, mdim, sdim, None)
            return P(None, data, mdim, None, None)
        if pstr.startswith("enc") and len(shape) == 3:  # whisper enc out
            return P(data if not long_ctx else None, None, None)
        if pstr.startswith("mamba"):
            bdim = None if long_ctx else (
                data if _div(shape[1], dsz) else None
            )
            if pstr.endswith("ssm") and len(shape) == 5:   # (L,B,H,P,N)
                mdim = ax.model if _div(shape[2], msz) else None
                return P(None, bdim, mdim, None, None)
            if len(shape) >= 2:
                return P(None, bdim, *([None] * (len(shape) - 2)))
        if pstr.startswith("xl"):
            bdim = None if long_ctx else (
                data if len(shape) >= 1 and _div(shape[0], dsz) else None
            )
            return P(bdim, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(rule, cache_shapes)


def to_shardings(spec_tree: Any, mesh) -> Any:
    mesh = auto_axes(mesh)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def fsdp_gather_specs(param_shapes: Any, cfg: ArchConfig, ax: MeshAxes,
                      mesh) -> dict[str, Any]:
    """Per-layer *gathered* shardings for the FSDP schedule.

    Takes the storage specs and strips the FSDP (data) axes, keeping
    the TP axis: inside the layer scan, weights are constrained to this
    sharding so GSPMD all-gathers one layer at a time (instead of
    all-reducing activations on every FSDP-sharded contraction).
    """
    full = param_specs(param_shapes, cfg, ax, mesh)
    data_names = set(ax.data)

    def strip(spec: P) -> P:
        parts = []
        for part in spec:
            if part is None:
                parts.append(None)
            elif isinstance(part, (tuple, list)):
                kept = tuple(a for a in part if a not in data_names)
                parts.append(kept if kept else None)
            else:
                parts.append(None if part in data_names else part)
        return P(*parts)

    out: dict[str, Any] = {}
    for group in ("blocks", "enc_blocks"):
        if isinstance(full, dict) and group in full:
            inner = jax.tree.map(
                lambda s: NamedSharding(mesh, strip(P(*s[1:]))),  # drop layer dim
                full[group], is_leaf=lambda x: isinstance(x, P),
            )
            out[group] = inner
    for group in ("shared", "xl_blocks"):
        if isinstance(full, dict) and group in full:
            out[group] = jax.tree.map(
                lambda s: NamedSharding(mesh, strip(s)),
                full[group], is_leaf=lambda x: isinstance(x, P),
            )
    # Residual-stream constraint: batch over the data axes, features
    # replicated (see transformer._maybe_constrain_act).
    data = ax.data if len(ax.data) > 1 else ax.data[0]
    out["__act__"] = NamedSharding(mesh, P(data, None, None))
    return out
