"""Multi-device expert-parallel serving plumbing (paper §5.2-5.3).

The engines stay single-host programs; this module gives them a device mesh:

  * ``build_serving_mesh`` — mesh + sharding rules from ``cfg.ep_mesh``:
    ``(8,)`` = flat EP over one axis; ``(4, 2)`` = ("pod", ep_axis) two-axis
    mesh whose MoE exchange runs the hierarchical two-hop all-to-all
    (paper Fig. 8).  Serving meshes carry no tensor-parallel axis — experts
    partition over ALL mesh axes, everything else replicates for aggregate
    memory bandwidth (§5.1).
  * ``init_engine_mesh`` — resolves the mesh and rewrites ``cfg.moe_impl``
    to the serving EP schedule (core/moe_serve.py): "grouped" →
    "ep_grouped", every capacity impl → "ep_serve".
  * ``place_params`` / ``place_caches`` — device_put with the rule-derived
    PartitionSpecs (parallel/params.py): expert wi/wg/wo sharded
    ``P(ep_axes, ...)``, non-expert params replicated; KV caches sharded
    over the slot dim when ``slots % ep == 0`` (attention data-parallel
    over slots) and replicated otherwise.  The paged block pool itself is
    replicated — each rank only *reads* the pages of its slot shard, and
    the host-side scheduler stays mesh-agnostic.
  * ``MeshCall`` — wraps each jitted engine entry point so calls, ``lower``
    and abstract traces all run under the engine's mesh (thread-local
    ambient mesh for shard_map / shard_hint), while forwarding attributes
    like ``_cache_size`` so the retrace watchdog and the analysis gate's
    compile-count prediction keep working unchanged.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding

from repro.parallel.sharding import make_mesh
from repro.parallel.sharding import DEFAULT_RULES, use_mesh


def parse_ep_mesh(text: str) -> Tuple[int, ...]:
    """'8' -> (8,); '4x2' -> (4, 2) (hosts x devices-per-host)."""
    try:
        shape = tuple(int(p) for p in text.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad --ep-devices {text!r}: expected '8' or '4x2'") from None
    if not shape or any(n < 1 for n in shape) or len(shape) > 2:
        raise ValueError(f"bad --ep-devices {text!r}: expected '8' or '4x2'")
    return shape


def build_serving_mesh(shape, *, ep_axis: str = "data"):
    """(mesh, rules) for an EP serving topology, or (None, None) when the
    shape is trivial.  1-d: flat EP over ``ep_axis``.  2-d: ("pod",
    ep_axis), outer (host) axis major — experts lay out outer-major, which
    is what the hierarchical all-to-all's stage split assumes."""
    shape = tuple(int(n) for n in (shape or ()))
    ndev = 1
    for n in shape:
        ndev *= n
    if not shape or ndev <= 1:
        return None, None
    if len(shape) == 1:
        names: Tuple[str, ...] = (ep_axis,)
        expert = ep_axis
    elif len(shape) == 2:
        names = ("pod", ep_axis)
        expert = names
    else:
        raise ValueError(f"ep_mesh supports 1 or 2 axes, got {shape}")
    avail = len(jax.devices())
    if ndev > avail:
        raise ValueError(
            f"ep_mesh={shape} needs {ndev} devices but only {avail} are "
            "visible (CPU testing: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={ndev})"
        )
    mesh = make_mesh(shape, names)
    rules = {**DEFAULT_RULES, "expert": expert, "batch": expert}
    return mesh, rules


def serving_moe_impl(impl: str) -> str:
    """Map a single-device moe_impl to its EP serving schedule."""
    if impl in ("ep_serve", "ep_grouped"):
        return impl
    return "ep_grouped" if impl == "grouped" else "ep_serve"


def init_engine_mesh(cfg):
    """(mesh, rules, cfg') for an engine: None/None/cfg when cfg.ep_mesh is
    trivial, else the serving mesh plus cfg with moe_impl rewritten to the
    EP schedule.  Must run BEFORE the engine captures cfg in its jit
    closures."""
    mesh, rules = build_serving_mesh(
        getattr(cfg, "ep_mesh", ()), ep_axis=getattr(cfg, "ep_axis", "data")
    )
    if mesh is None:
        return None, None, cfg
    return mesh, rules, cfg.replace(moe_impl=serving_moe_impl(cfg.moe_impl))


def ep_degree(mesh) -> int:
    return 1 if mesh is None else int(mesh.devices.size)


def _place(mesh, tree, specs):
    """device_put each leaf of ``tree`` with the matching PartitionSpec leaf
    of ``specs`` (same structure; specs leaves are PartitionSpec, which is
    itself a tuple pytree — flatten_up_to keeps them atomic)."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    flat_specs = treedef.flatten_up_to(specs)
    placed = [
        jax.device_put(leaf, NamedSharding(mesh, s)) for leaf, s in zip(flat, flat_specs)
    ]
    return jax.tree_util.tree_unflatten(treedef, placed)


def place_params(mesh, rules, params):
    """Commit params to the mesh: experts P(ep_axes, ...) per-device, the
    rest replicated everywhere (paper §5.1 aggregate memory bandwidth)."""
    from repro.parallel.params import param_pspecs

    with use_mesh(mesh, rules):
        specs = param_pspecs(mesh, params, mode="serve")
    return _place(mesh, params, specs)


def place_caches(mesh, rules, caches, *, slots: int, n_pages: Optional[int] = None):
    """Commit KV caches: slot (batch) dim sharded over the EP axes when
    divisible, everything else replicated.  The [n_pages+1, ...] pool leaves
    have no slot dim and replicate; when a degenerate config makes
    ``n_pages + 1 == slots`` the shape test can't tell pool from per-slot
    leaves, so everything replicates (correct, just not slot-parallel)."""
    from repro.parallel.params import cache_pspecs

    batch = -1 if (n_pages is not None and n_pages + 1 == slots) else slots
    with use_mesh(mesh, rules):
        specs = cache_pspecs(mesh, caches, batch)
    return _place(mesh, caches, specs)


def placed_param_bytes(params) -> int:
    """Per-device bytes of a placed param tree (addressable shards only) —
    the benchmark's 'per-device expert bytes' evidence."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            sh = shards[0]
            total += sh.data.size * sh.data.dtype.itemsize
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total


class MeshCall:
    """Callable wrapper keeping a jitted engine entry point inside the
    engine's mesh context for every interaction the analysis gate and the
    watchdog have with it: __call__ (execution, jax.eval_shape,
    jax.make_jaxpr), lower() (donation audit), and attribute forwarding
    (_cache_size for retrace accounting)."""

    def __init__(self, fn, mesh, rules):
        self._fn = fn
        self._mesh = mesh
        self._rules = rules

    def __call__(self, *args, **kw):
        with use_mesh(self._mesh, self._rules):
            return self._fn(*args, **kw)

    def lower(self, *args, **kw):
        with use_mesh(self._mesh, self._rules):
            return self._fn.lower(*args, **kw)

    def __getattr__(self, name):
        return getattr(self._fn, name)
