#!/usr/bin/env python3
"""Compile rehearsal of each cell's two serving programs — the engine's
paged decode step and its batched chunk prefill — at the cell's sizes, for
a described TPU v5e (one chip; ``v5e:2x2`` for a cell on four chips).
Nothing runs: it prints ``memory_analysis()`` of each program (arguments,
outputs, temporaries, aliased bytes per device).  The compiler refuses a
program that does not fit the chip's HBM (RESOURCE_EXHAUSTED, with the
overshoot), which is how each configuration's ``n_pages`` was sized:
``--pages`` tries another pool.  These are compile-time numbers, never a
chip measurement.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload NAME] [--pages N]
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def rehearse(name: str, n_pages: int | None) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    import repro.core.moe as moe
    import repro.kernels.ops as ops
    import repro.models.attention as attention
    from repro.models import model as M
    from repro.parallel.params import cache_pspecs
    from repro.parallel.sharding import DEFAULT_RULES, make_mesh, use_mesh

    from bench import catalog, program

    c = catalog.cell(name)
    conf, serving = c["config"], dict(c["config"]["serving"])
    if n_pages:
        serving["n_pages"] = n_pages
    chips = c["workload"]["chips"]
    # the program's TPU paths, as a chip would take them
    ops._interpret = lambda: False
    attention.PAGED_BACKEND[0] = "kernel"
    moe.GROUPED_EXPERT_BACKEND[0] = "kernel"
    cfg = program.model_config(conf)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    S, cap, ps, C = serving["slots"], serving["capacity"], serving["page_size"], serving["prefill_chunk"]
    mp = -(-cap // ps)
    params_abs = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    caches_abs = jax.eval_shape(lambda: M.init_paged_caches(
        cfg, S, cap, n_pages=serving["n_pages"], page_size=ps))

    if chips == 1:
        mesh, rules = None, None
        one = SingleDeviceSharding(topo.devices[0])
        shard = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
        p_abs, c_abs = shard(params_abs), shard(caches_abs)
        rep = one
        run_cfg = cfg
    else:
        from repro.serving.ep import serving_moe_impl

        mesh = make_mesh(cfg.ep_mesh, (cfg.ep_axis,), devices=topo.devices[:chips])
        rules = {**DEFAULT_RULES, "expert": cfg.ep_axis, "batch": cfg.ep_axis}
        run_cfg = cfg.replace(moe_impl=serving_moe_impl(cfg.moe_impl))
        psh = program.param_shardings(cfg, mesh, rules, params_abs)
        p_abs = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), params_abs, psh)
        with use_mesh(mesh, rules):
            cspec = cache_pspecs(mesh, caches_abs, S)
        c_abs = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
                             caches_abs, cspec, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        rep = NamedSharding(mesh, PartitionSpec())
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    def decode(params, tokens, positions, active, caches, tables):
        return M.paged_ragged_decode_step(run_cfg, params, tokens, positions, active, caches, tables)

    def prefill(params, tokens, positions, reset, active, last_idx, caches, tables):
        return M.paged_prefill_chunk_batched(run_cfg, params, tokens, positions, reset, active,
                                             last_idx, caches, tables, capacity=cap, page_size=ps)

    progs = {
        "decode": (jax.jit(decode, donate_argnums=(4,)),
                   (p_abs, arr((S, 1), jnp.int32), arr((S,), jnp.int32), arr((S,), bool), c_abs,
                    arr((S, mp), jnp.int32))),
        "prefill_batched": (jax.jit(prefill, donate_argnums=(6,)),
                            (p_abs, arr((S, C), jnp.int32), arr((S, C), jnp.int32), arr((S,), bool),
                             arr((S,), bool), arr((S,), jnp.int32), c_abs, arr((S, mp), jnp.int32))),
    }
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(caches_abs))
    print(f"{name}: {conf['name']} on {chips} chip(s), {S} slots x {cap} tokens, "
          f"{serving['n_pages']} pages of {ps}: pool {pool / 1e9:.3f} GB "
          f"({pool / (serving['n_pages'] + 1):.0f} B per page)", flush=True)
    for pname, (fn, args) in progs.items():
        if mesh is not None:
            with use_mesh(mesh, rules):
                compiled = fn.lower(*args).compile()
        else:
            compiled = fn.lower(*args).compile()
        ma = compiled.memory_analysis()
        kernels = compiled.as_text().count("tpu_custom_call")
        print(f"  {pname}: compiles within one chip's HBM; arguments "
              f"{ma.argument_size_in_bytes / 1e9:.3f} GB, outputs {ma.output_size_in_bytes / 1e9:.3f} GB "
              f"(aliased {ma.alias_size_in_bytes / 1e9:.3f} GB), temporaries "
              f"{ma.temp_size_in_bytes / 1e9:.3f} GB per device; {kernels} tpu_custom_call sites",
              flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--pages", type=int, default=None, help="rehearse this pool size instead")
    args = ap.parse_args()
    from bench import catalog

    names = args.workload or [w["name"] for w in catalog.load_benchmark()["workloads"]]
    for name in names:
        rehearse(name, args.pages)
    return 0


if __name__ == "__main__":
    sys.exit(main())
