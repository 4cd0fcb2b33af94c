"""Sharding rules: logical param axes -> mesh axes -> PartitionSpec trees.

Mesh conventions (launch/mesh.py):
  single-pod: (16, 16) axes ("data", "model")
  multi-pod : (2, 16, 16) axes ("pod", "data", "model")

Rule sets map the logical axis names used by ParamFactory to mesh axes.
A mesh axis is applied to a tensor dim only when the dim is divisible by
the axis size (vocab sizes like 49155 or head counts like 24 are not
16-divisible — those dims fall back to replicated, exactly what GSPMD
would do anyway, but made explicit here).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.factory import is_abstract_leaf


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler), the one mesh construction point of the repo."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=tuple(jax.sharding.AxisType.Auto for _ in axis_names))


# ------------------------------------------------- federated client axis ---

def client_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D ``("clients",)`` mesh over the host's devices — the batched
    async engine's data-parallel axis.  Stacked per-client state (leading
    axis = client) sharded on it runs each scheduler window's vmapped
    local update as pure data parallelism: every device trains its slice
    of the federation, no cross-device collectives in the update itself."""
    n = num_devices if num_devices is not None else len(jax.devices())
    return make_mesh((n,), ("clients",))


def client_state_sharding(num_clients: int,
                          mesh: Optional[Mesh] = None) -> NamedSharding:
    """``NamedSharding`` for stacked per-client pytrees: dim0 over
    ``"clients"``, everything else replicated (``P("clients")`` names only
    the leading dim).  Raises ``ValueError`` when the device count does
    not divide the client count: asking for sharded client state and
    silently getting a replicated run is never what the caller meant.
    A single-device mesh is a valid degenerate case: the constraint is a
    no-op there, which is what keeps the sharded engine bit-exact with
    the unsharded one (tests/test_async_engine.py)."""
    mesh = mesh if mesh is not None else client_mesh()
    ndev = int(mesh.devices.size)
    if num_clients % ndev:
        raise ValueError(
            f"shard_clients: {num_clients} clients do not divide over "
            f"{ndev} devices; choose num_clients as a multiple of {ndev}")
    return NamedSharding(mesh, P("clients"))

# FSDP x TP: d_model dim sharded over data (ZeRO-style), ff/heads/vocab over
# model (tensor parallel); experts over model (expert parallel).
TRAIN_RULES: Dict[str, Optional[str]] = {
    "embed": "data",
    "mlp": "model",
    "heads": "model",
    "vocab": "model",
    "expert": "model",
    "qlora": None,   # wq_b is (qlora, heads): heads takes the model axis
    "kvlora": None,
}

# Serving: weights TP-sharded on model, replicated over data (no optimizer
# state to amortise; batch parallelism over data).
SERVE_RULES: Dict[str, Optional[str]] = {
    "embed": None,
    "mlp": "model",
    "heads": "model",
    "vocab": "model",
    "expert": "model",
    "qlora": None,
    "kvlora": None,
}


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def spec_for(shape, axes, rules, mesh: Mesh) -> P:
    """First dim that can legally take a mesh axis wins it: a later logical
    axis mapping to an already-used mesh axis is dropped (e.g. MoE expert
    weights (E, d, ff) with E and ff both -> "model": E takes it when the
    expert count divides, otherwise ff inherits it — granite's E=40 falls
    back to ff-dim tensor parallelism while qwen's E=128 expert-shards)."""
    parts = []
    used = set()
    for dim, ax in zip(shape, axes):
        mesh_ax = rules.get(ax) if ax is not None else None
        if (mesh_ax is not None and mesh_ax in mesh.axis_names
                and mesh_ax not in used
                and dim % _axis_size(mesh, mesh_ax) == 0):
            parts.append(mesh_ax)
            used.add(mesh_ax)
        else:
            parts.append(None)
    return P(*parts)


def param_specs(abstract_tree, rules, mesh: Mesh):
    """AbstractParam tree -> PartitionSpec tree."""
    return jax.tree.map(
        lambda a: spec_for(a.shape, a.axes, rules, mesh),
        abstract_tree, is_leaf=is_abstract_leaf)


def param_shardings(abstract_tree, rules, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_specs(abstract_tree, rules, mesh),
        is_leaf=lambda x: isinstance(x, P))


def batch_spec(shape, mesh: Mesh, *, batch_axes=("data",), seq_axis=None) -> P:
    """Shard dim0 (global batch) over batch_axes (divisibility-guarded),
    optionally dim1 (sequence) over seq_axis."""
    usable = [a for a in batch_axes if a in mesh.axis_names]
    bsz = int(np.prod([_axis_size(mesh, a) for a in usable])) if usable else 1
    d0 = tuple(usable) if usable and shape[0] % bsz == 0 else None
    parts = [d0]
    if len(shape) > 1:
        if seq_axis and seq_axis in mesh.axis_names and shape[1] % _axis_size(mesh, seq_axis) == 0:
            parts.append(seq_axis)
        else:
            parts.append(None)
    parts += [None] * (len(shape) - len(parts))
    return P(*parts)


def cache_spec(shape, mesh: Mesh) -> P:
    """Decode KV cache: (B, C, KV, hd) — batch over data, cache length over
    model (flash-decoding style; GSPMD turns softmax/contraction over the
    sharded length into small all-reduces).  Divisibility-guarded."""
    parts = [None] * len(shape)
    if "data" in mesh.axis_names and shape[0] % _axis_size(mesh, "data") == 0:
        parts[0] = "data"
    if len(shape) > 1 and "model" in mesh.axis_names and shape[1] % _axis_size(mesh, "model") == 0:
        parts[1] = "model"
    return P(*parts)
