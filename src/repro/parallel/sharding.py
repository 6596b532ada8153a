"""Sharding rules: logical-axis → mesh-axis mapping and helpers.

The production mesh is ``(pod=2,) data=16, model=16`` (launch/mesh.py).  The
paper-faithful DS-MoE scheme (DESIGN.md §4):

  * batch                 -> ('pod', 'data')
  * attention heads, d_ff -> 'model'            (Megatron tensor-slicing)
  * expert dim E          -> 'data'             (expert parallelism, EP=16)
  * expert d_ff           -> 'model'            (paper's *expert-slicing*)
  * vocab                 -> 'model'
  * everything else       -> replicated

GQA kv-heads and odd dims (glm4 kv=2, internvl2 H=14) are sharded only when
divisible by the mesh axis — ``maybe_shard`` implements that rule.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _axis_sizes() -> dict:
    mesh = get_mesh()
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, mesh.devices.shape))


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Activate a mesh for ``shard_hint``/``spec`` helpers (and as the jax
    ambient mesh for shard_map)."""
    prev = getattr(_state, "mesh", None)
    prev_rules = getattr(_state, "rules", None)
    _state.mesh = mesh
    _state.rules = rules or DEFAULT_RULES
    try:
        yield mesh
    finally:
        _state.mesh = prev
        _state.rules = prev_rules


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: GSPMD propagates shardings
    and ``shard_hint`` constraints steer it, which is how every mesh in this
    repo is used."""
    n = len(tuple(axis_shapes))
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * n, devices=devices,
    )


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def get_rules() -> dict:
    return getattr(_state, "rules", None) or DEFAULT_RULES


# Logical axis names used throughout model code.
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "data",
    "expert_mlp": "model",  # expert-slicing (paper §5.2)
    # context-parallel fallback: when an arch's head count doesn't divide the
    # 'model' axis (llama4: 40 heads, internvl2: 14), attention would run
    # fully replicated across TP ranks; sharding the query sequence instead
    # distributes it (EXPERIMENTS.md §Perf, llama4-prefill iteration).
    "q_seq": "model",
    "state": None,
    "layers": None,  # stacked-scan leading axis
}

# Alternative rule-sets used by perf experiments (see EXPERIMENTS.md §Perf).
RULESETS = {
    "default": DEFAULT_RULES,
    # Naive baseline: experts spread over *all* chips (flat EP=256) — the
    # paper's "PyTorch baseline" analogue where the a2a spans p devices.
    "flat_ep": {**DEFAULT_RULES, "expert": ("data", "model"), "expert_mlp": None},
    # Cross-pod expert parallelism with the paper's hierarchical a2a (Fig. 8):
    # experts over (pod, data) = EP 32, intra-pod + inter-pod two-stage a2a.
    "ep_pod": {**DEFAULT_RULES, "expert": ("pod", "data")},
    # Sequence-parallel long decode: KV cache sequence dim over 'data'.
    "seqpar_kv": {**DEFAULT_RULES, "kv_seq": "data"},
}


def _filter_axes(mesh_axes, dim_size: int, taken: set):
    """Return mesh axes (possibly a sub-tuple) that evenly divide dim_size."""
    if mesh_axes is None:
        return None
    sizes = _axis_sizes()
    if isinstance(mesh_axes, str):
        mesh_axes = (mesh_axes,)
    picked = []
    prod = 1
    for ax in mesh_axes:
        if ax in taken or ax not in sizes:
            continue
        if dim_size % (prod * sizes[ax]) == 0:
            picked.append(ax)
            prod *= sizes[ax]
    if not picked:
        return None
    return tuple(picked) if len(picked) > 1 else picked[0]


def spec(*logical_axes, shape=None) -> P:
    """Build a PartitionSpec from logical axis names, respecting divisibility
    when ``shape`` is given."""
    rules = get_rules()
    out = []
    taken: set = set()
    for i, name in enumerate(logical_axes):
        axes = rules.get(name) if name is not None else None
        if shape is not None:
            axes = _filter_axes(axes, shape[i], taken)
        if axes is not None:
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                taken.add(a)
        out.append(axes)
    return P(*out)


def shard_hint(x: jax.Array, *logical_axes) -> jax.Array:
    """``with_sharding_constraint`` if a mesh is active, identity otherwise."""
    mesh = get_mesh()
    if mesh is None:
        return x
    s = spec(*logical_axes, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))


def named_sharding(*logical_axes, shape=None) -> Optional[NamedSharding]:
    mesh = get_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, spec(*logical_axes, shape=shape))
