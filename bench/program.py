"""The system under test, built from a configuration file.

Everything the benchmark takes from the program goes through this module:
the model config builder the file names, ``init_params`` (weights made on
the device from the seed, directly in their mesh layout on several chips),
the serving mesh builder, and ``ContinuousEngine``.  Nothing here measures
or judges; that is the rest of ``bench/``.
"""
from __future__ import annotations

import importlib

import jax

from bench.catalog import expand_layers
from bench.seeds import weights_key


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    layer by layer against the layers the file states (the reference reads
    those, so the two cannot drift apart)."""
    b = conf["builder"]
    mod, fn = b["fn"].split(":")
    cfg = getattr(importlib.import_module(mod), fn)(*b.get("args", []), **b.get("kwargs", {}))
    serving = conf["serving"]
    cfg = cfg.replace(moe_impl=serving["moe_impl"], ep_mesh=tuple(serving["ep_mesh"]))
    want = expand_layers(conf)
    got = [
        {"ffn": ls.ffn.kind, "experts": ls.ffn.num_experts, "residual": ls.ffn.residual,
         "act": ls.ffn.act, "d_ff": ls.ffn.d_ff, "top_k": ls.ffn.top_k}
        for ls in cfg.layer_specs()
    ]
    for i, (g, w) in enumerate(zip(got, want)):
        for k in g:
            if k in w and g[k] != w[k]:
                raise ValueError(f"{conf['name']}: layer {i} {k} is {g[k]} in the program, "
                                 f"{w[k]} in the configuration file")
    checks = {"d_model": cfg.d_model, "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
              "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
              "num_hidden_layers": len(got), "max_seq_len": cfg.max_seq_len,
              "param_dtype": cfg.param_dtype, "tie_embeddings": cfg.tie_embeddings}
    for k, v in checks.items():
        if conf[k] != v:
            raise ValueError(f"{conf['name']}: {k} is {v} in the program, {conf[k]} in the file")
    return cfg


def serving_mesh(cfg):
    """(mesh, rules) of the configuration's EP mesh, or (None, None)."""
    from repro.serving.ep import build_serving_mesh

    return build_serving_mesh(cfg.ep_mesh, ep_axis=cfg.ep_axis)


def param_shardings(cfg, mesh, rules, shapes):
    """NamedShardings of the serving layout: experts split over the mesh,
    everything else replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.parallel.params import param_pspecs
    from repro.parallel.sharding import use_mesh

    with use_mesh(mesh, rules):
        specs = param_pspecs(mesh, shapes, mode="serve")
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, PartitionSpec))


def make_params(cfg, seed: int, mesh=None, rules=None):
    """Weights made on the device in one jitted call, in the dtype they are
    served in; on a mesh, made in place in the serving layout (the 13B
    model never fits one chip, so it is never gathered)."""
    from repro.models.model import init_params

    make = lambda key: init_params(cfg, key)
    key = weights_key(seed)
    if mesh is None:
        return jax.jit(make)(key)
    out = param_shardings(cfg, mesh, rules, jax.eval_shape(make, key))
    return jax.jit(make, out_shardings=out)(key)


def build_engine(cfg, params, serving: dict):
    from repro.configs.base import PagedKVConfig
    from repro.serving.continuous import ContinuousEngine

    return ContinuousEngine(
        cfg, params, slots=serving["slots"], capacity=serving["capacity"],
        paged_cfg=PagedKVConfig(page_size=serving["page_size"], n_pages=serving["n_pages"],
                                prefill_chunk=serving["prefill_chunk"]),
        prefill_mode="batched",
    )


def request(prompt, max_new_tokens: int):
    from repro.serving.engine import Request

    return Request(prompt=list(prompt), max_new_tokens=int(max_new_tokens))
