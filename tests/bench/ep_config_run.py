"""Child process of test_ep_config.py: a MoE-128-shaped configuration (no
residual MLP, 16 experts, 4 per device) on a (4,) EP mesh of four host CPU
devices.  Prints one JSON line with three checks:

* ``served``: served through paged batched prefill and decode, the kept
  logits against the plain float32 reference (``correct.errors``);
* ``shares``: with the exchange left out (``psum`` made the identity), each
  device's partial MoE layer output, and their sum over the four devices
  against the single-device layer's output;
* ``ep_rows``: the engine's per-tick ``ep_rows`` counter against the rows
  per expert that the decode step's routing statistics give (the gating
  re-run on the step's replicated rows), summed by device on the host.
"""
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CHIPS = 4


def served(conf: dict, seed: int) -> dict:
    """Serve a fixed set of requests on the mesh through the benchmark's
    engine settings, with the decode step's routing statistics on: each
    tick, per MoE layer, the gating re-run on the step's replicated rows
    gives the rows per expert, which the engine's ``ep_rows`` counter must
    sum by device."""
    from bench import correct, program
    from repro.configs.base import PagedKVConfig
    from repro.obs import Obs
    from repro.serving import continuous

    cfg = program.model_config(conf)
    mesh, rules = program.serving_mesh(cfg)
    sv = conf["serving"]
    engine = continuous.ContinuousEngine(
        cfg, program.make_params(cfg, seed, mesh, rules), slots=sv["slots"], capacity=sv["capacity"],
        paged_cfg=PagedKVConfig(page_size=sv["page_size"], n_pages=sv["n_pages"],
                                prefill_chunk=sv["prefill_chunk"]),
        prefill_mode="batched", obs=Obs(routing=True))
    per_tick = []
    summarize = continuous.summarize_routing

    def keep(stats):
        out = summarize(stats)
        per_tick.append([v["tokens_per_expert"] for _, v in sorted(out["per_layer"].items(),
                                                                   key=lambda kv: _layer(kv[0]))])
        return out

    continuous.summarize_routing = keep
    kept = correct.ServedLogits(engine)
    rng = np.random.default_rng(seed)
    reqs = [rng.integers(0, conf["vocab_size"], size=n).astype(np.int32)
            for n in (23, 70, 41, 96, 12, 55)]
    ids = [engine.submit(program.request(p, 12)) for p in reqs]
    done = engine.run_until_done()
    samples = [(p, np.asarray(done[i].tokens, np.int32), 12, kept.of(i, len(done[i].tokens)))
               for p, i in zip(reqs, ids)]
    ticks = [m for m in engine.metrics_log if m["tokens_this_tick"]]
    want = [[np.asarray(t).reshape(CHIPS, -1).sum(1).tolist() for t in layers] for layers in per_tick]
    return {"errors": correct.errors(conf, seed, samples)[0], "ticks": ticks, "want": want,
            "params": engine.params, "cfg": cfg}


def _layer(key: str) -> tuple:
    """``seg0/pos1[3]`` -> (segment, repeat, position): layer order."""
    seg, rest = key.split("/")
    pos, rep = rest.rstrip("]").split("[")
    return int(seg[3:]), int(rep), int(pos[3:])


def shares(cfg, params, seed: int) -> dict:
    """The first MoE layer on a [3, 5] batch: each device's partial output
    with the exchange left out, summed over the devices, against the
    single-device grouped layer; and the layer with its exchange."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import moe_serve
    from repro.core.moe import moe_layer
    from repro.parallel.sharding import use_mesh
    from repro.serving.ep import build_serving_mesh

    seg = cfg.segments[0]
    j = next(j for j, ls in enumerate(seg.pattern) if ls.ffn.kind == "moe")
    spec = seg.pattern[j].ffn
    p = jax.tree.map(lambda a: jnp.asarray(a[0]), params["segments"]["seg0"][f"pos{j}"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (3, 5, cfg.d_model), jnp.float32).astype(jnp.bfloat16)
    single = moe_layer(cfg.replace(ep_mesh=(), moe_impl="grouped"), spec, p, x)[0]
    mesh, rules = build_serving_mesh(cfg.ep_mesh, ep_axis=cfg.ep_axis)
    axes = (cfg.ep_axis,)

    def body(x, router, wi, wo):
        y = moe_serve._body_replicated_grouped(cfg, spec, axes, x, router, wi, wi, wo)[0]
        return y[None]

    w = P(cfg.ep_axis, None, None)
    psum = jax.lax.psum
    jax.lax.psum = lambda v, axes, **kw: v
    try:
        parts = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), w, w),
                                      out_specs=P(cfg.ep_axis), check_vma=False))(
            x, p["router"], p["wi"], p["wo"])
    finally:
        jax.lax.psum = psum
    with use_mesh(mesh, rules):
        whole = jax.jit(lambda p, x: moe_layer(cfg.replace(moe_impl="ep_grouped"), spec, p, x)[0])(p, x)
    parts = np.asarray(parts, np.float32)
    single, whole = np.asarray(single, np.float32), np.asarray(whole, np.float32)
    return {"parts": len(parts),
            "devices_contributing": int(sum(np.abs(q).sum() > 0 for q in parts)),
            "sum_vs_single": float(np.abs(parts.sum(0) - single).max()),
            "whole_vs_single": float(np.abs(whole - single).max()),
            "single_max": float(np.abs(single).max())}


def main() -> int:
    conf, seed = json.loads(Path(sys.argv[1]).read_text()), int(sys.argv[2])
    s = served(conf, seed)
    got, want = [m["ep_rows"] for m in s["ticks"]], s["want"]
    print(json.dumps({
        "served": s["errors"],
        "shares": shares(s["cfg"], s["params"], seed),
        "ep_rows": {"ticks": len(got), "replayed": len(want), "got": got, "want": want,
                    "slots": conf["serving"]["slots"]},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
