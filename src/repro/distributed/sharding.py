"""Logical-axis sharding rules (MaxText-style) mapping model-declared axis
names to mesh axes, per run mode.

Model code annotates every parameter dimension with a logical name
(``repro.models.*`` init functions return an ``axes`` tree).  This module
turns those annotations into ``NamedSharding`` trees for pjit, with:

* per-mode rule tables (train = FSDP×TP, serve = TP, + pure-DP across pods),
* arch-aware MoE rule (experts ≥ |model| → expert parallelism; otherwise
  TP inside each expert's FFN),
* conflict sanitation (a mesh axis may appear at most once per spec; later
  occurrences are dropped deterministically),
* divisibility checks (a dim only shards if the mesh axis divides it).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

MeshAxes = tuple[str, ...] | str | None


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis pinned Auto (the sharding rules
    below rely on the automatic partitioner)."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kwargs)


def abstract_mesh(axis_shapes, axis_names) -> jax.sharding.AbstractMesh:
    """Device-free mesh of the given (sizes, names) for shape-only rules."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names))


def _mesh_size(mesh, name: str) -> int:
    return dict(mesh.shape)[name]


@dataclasses.dataclass(frozen=True)
class Rules:
    """logical axis name -> mesh axes (None = replicated)."""

    table: Mapping[str, MeshAxes]

    def get(self, logical: str) -> MeshAxes:
        return self.table.get(logical)


def train_rules(cfg: ModelConfig, mesh: Mesh) -> Rules:
    """FSDP(data) × TP(model); the pod axis stays pure-DP (gradients cross
    pods once per step — the slow-link-friendly choice; see DESIGN.md §5)."""
    model_n = _mesh_size(mesh, "model")
    ep = cfg.n_experts >= model_n  # expert parallelism vs TP-in-expert
    table = {
        # embeddings: vocab on model, d_model FSDP on data
        "vocab": "model",
        "embed": "data",
        # attention: heads on model (TP)
        "heads": "model",
        "kv_heads": None,
        "head_dim": None,
        # dense mlp: ff on model
        "mlp": "model",
        # MoE
        "experts": "model" if ep else None,
        "expert_mlp": None if ep else "model",
        "experts_r": None,
        # mamba
        "ssm_proj": "model",
        "ssm_conv_ch": "model",
        "ssm_inner": "model",
        "ssm_heads": None,
        "conv_k": None,
        # stacking axes
        "layers": None,
        "periods": None,
    }
    return Rules(table)


def serve_rules(cfg: ModelConfig, mesh: Mesh) -> Rules:
    """Pure TP for weights (replicated over data/pod); KV caches shard batch
    on data and sequence-blocks on model (flash-decoding style SP)."""
    model_n = _mesh_size(mesh, "model")
    ep = cfg.n_experts >= model_n
    table = {
        "vocab": "model",
        "embed": None,
        "heads": "model",
        "kv_heads": None,
        "head_dim": None,
        "mlp": "model",
        "experts": "model" if ep else None,
        "expert_mlp": None if ep else "model",
        "experts_r": None,
        "ssm_proj": "model",
        "ssm_conv_ch": "model",
        "ssm_inner": "model",
        "ssm_heads": None,
        "conv_k": None,
        "layers": None,
        "periods": None,
    }
    return Rules(table)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """The batch-parallel mesh axes: ("pod","data") on multi-pod meshes."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# spec construction
# ---------------------------------------------------------------------------


def spec_for_axes(axes: tuple[str, ...], shape: tuple[int, ...],
                  rules: Rules, mesh: Mesh) -> P:
    """Build a sanitized PartitionSpec for one array."""
    used: set[str] = set()
    entries: list[MeshAxes] = []
    for dim, logical in enumerate(axes):
        target = rules.get(logical)
        if target is None:
            entries.append(None)
            continue
        target_t = (target,) if isinstance(target, str) else tuple(target)
        # drop axes already used or not dividing the dim
        kept = []
        size = 1
        for a in target_t:
            n = _mesh_size(mesh, a)
            if a in used:
                continue
            if shape[dim] % (size * n) != 0:
                continue
            kept.append(a)
            size *= n
        for a in kept:
            used.add(a)
        # Singleton axes unwrap to the bare name: PartitionSpec("x") is the
        # canonical spelling of PartitionSpec(("x",)).
        entries.append(kept[0] if len(kept) == 1 else tuple(kept) if kept else None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def make_param_shardings(axes_tree, shapes_tree, rules: Rules, mesh: Mesh):
    """axes_tree mirrors the params tree with logical-axis tuples as leaves;
    shapes_tree provides the corresponding shapes (ShapeDtypeStruct tree)."""

    def one(axes, arr):
        spec = spec_for_axes(axes, arr.shape, rules, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, axes_tree, shapes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, str) for e in x))


def batch_sharding(mesh: Mesh, sds) -> NamedSharding:
    """[B, ...] inputs: batch over ("pod","data"), honoring divisibility
    (batch=1 long-context shapes stay replicated)."""
    da = data_axes(mesh)
    n = int(np.prod([_mesh_size(mesh, a) for a in da])) if da else 1
    shape = sds.shape if hasattr(sds, "shape") else ()
    if not shape or shape[0] % n != 0:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(da, *([None] * (len(shape) - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Ambient activation constraints (set by the step builders at trace time)
# ---------------------------------------------------------------------------

_AMBIENT: dict = {"mesh": None}


def set_ambient_mesh(mesh) -> None:
    _AMBIENT["mesh"] = mesh


def constrain(x, *entries):
    """with_sharding_constraint against the ambient mesh; no-op without one.
    Entry "__data__" expands to the mesh's data axes tuple.

    A 1-device mesh (``make_host_mesh`` on a single-device host, or an
    explicit ``--mesh 1,1``) is also a no-op: every constraint it could
    express is full replication, and emitting them would still leave
    sharding-constraint ops in the jaxpr of single-device runs — the
    ambient mesh must leave those runs byte-for-byte untouched."""
    mesh = _AMBIENT["mesh"]
    if mesh is None or isinstance(mesh, jax.sharding.AbstractMesh):
        return x
    if int(np.prod([_mesh_size(mesh, a) for a in mesh.axis_names])) <= 1:
        return x
    da = data_axes(mesh)
    resolved = []
    for e in entries:
        if e == "__data__":
            if not da or x.shape[len(resolved)] % int(
                    np.prod([_mesh_size(mesh, a) for a in da])) != 0:
                resolved.append(None)
            else:
                resolved.append(da)
        elif isinstance(e, str) and e in mesh.axis_names:
            resolved.append(e if x.shape[len(resolved)] % _mesh_size(mesh, e) == 0
                            else None)
        else:
            resolved.append(e)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


# ---------------------------------------------------------------------------
# KV-cache / decode-state shardings (serving)
# ---------------------------------------------------------------------------


def cache_shardings(state_shapes, mesh: Mesh):
    """Shard decode state by leaf name (path-aware):

      kv k/v_store + scales : [L, B, Hkv, NB, ...] -> batch→data axes,
                              NB→"model" (sequence parallelism: the paper's
                              compression blocks are the SP sharding unit)
      kv k/v_buf            : [L, B, Hkv, T, D]    -> batch→data
      kv scalars + page_tab : [L] / [L, B, NB]     -> replicated
      ssm "conv"            : [..., B, K, C]       -> batch→data, C→"model"
      ssm "ssm"             : [..., B, H, N, P]    -> batch→data, H→"model"

    Any axis that fails divisibility falls back to replication — which is
    also how paged arenas (store batch extent 1, DESIGN.md §10) degrade
    gracefully: the batch rule can't divide 1, so the shared arena
    replicates while its page axis still shards on "model".
    """
    da = data_axes(mesh)
    da_n = int(np.prod([_mesh_size(mesh, a) for a in da])) if da else 1
    model_n = _mesh_size(mesh, "model")

    store_names = {"k_store", "v_store", "k_min", "k_step", "v_min", "v_step"}
    buf_names = {"k_buf", "v_buf"}

    def one(path, x):
        shp = x.shape
        nd = len(shp)
        names = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        leaf = names[-1] if names else None
        spec = [None] * nd

        def set_if(idx, axes, div):
            if 0 <= idx < nd and shp[idx] % div == 0 and shp[idx] >= div:
                spec[idx] = axes

        if leaf in store_names and nd >= 4:
            set_if(1, da, da_n)
            set_if(3, "model", model_n)  # NB (compression-block) axis
        elif leaf in buf_names and nd >= 4:
            set_if(1, da, da_n)
        elif leaf == "conv" and nd >= 3:
            set_if(nd - 3, da, da_n)
            set_if(nd - 1, "model", model_n)
        elif leaf == "ssm" and nd >= 4:
            set_if(nd - 4, da, da_n)
            set_if(nd - 3, "model", model_n)
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, state_shapes)
