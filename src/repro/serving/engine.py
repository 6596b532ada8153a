"""DS-MoE-style inference engine (paper §5): batched prefill + decode with
jitted steps, static shapes (padded request batches), KV/state caches, and
the multi-GPU parallelism layout applied through the active mesh.

The paper's design goals map as:
  * "group tokens with the same critical data path" -> dense-dispatch /
    expert-parallel MoE blocks inside ``decode_step`` (core/moe_parallel.py)
  * "aggregate memory bandwidth across devices"      -> params sharded per
    DESIGN.md §4; per-device bytes measured in benchmarks/fig10.
  * batching: requests are right-aligned into a fixed [B, S_max] prompt
    buffer; finished rows keep decoding into a scrap column (static shapes)
    and are masked out of the responses.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.gating import summarize_routing
from repro.models.model import decode_step, encode, init_caches, prefill
from repro.serving.sampling import sample


@dataclass
class EngineConfig:
    max_batch: int = 8
    max_prefill: int = 256
    max_decode: int = 64
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0  # nucleus sampling mass; 0 = off
    eos_id: int = -1  # -1: never stop early
    pad_id: int = 0
    kv_cache_bits: int = 0  # 0 = fp cache, 8 = int8 QuantizedKV (quant/kv.py)
    # Paged KV pool knobs, consumed by the continuous-serving path
    # (serve.py --paged -> ContinuousEngine; see configs.base.PagedKVConfig).
    # The static batched Engine always uses contiguous per-row caches.
    page_size: int = 0  # >0 = serve with a paged block pool
    n_pages: int = 0  # 0 = auto (slots * pages-per-capacity, no oversubscription)
    prefix_sharing: bool = False  # refcounted CoW page sharing (needs page_size > 0)
    prefill_chunk: int = 0  # admission-prefill tokens per tick (0 = auto: max(64, page_size))
    # Draft-then-verify speculative decoding (serving/spec.py): registry arch
    # name of the dense drafter, or "self" for the drafter==target oracle.
    # Greedy-only; needs page_size > 0. "" = off.
    spec_draft: str = ""
    spec_k: int = 4  # drafted tokens per verify window


@dataclass
class Request:
    prompt: Sequence[int]
    max_new_tokens: int = 16
    # when the request was due, on the ``time.perf_counter`` clock; the
    # continuous engine's queue-wait and TTFT timers start here when it is
    # set (a caller that submits late still charges the wait), else at
    # ``submit()``
    arrival_s: Optional[float] = None


@dataclass
class Response:
    tokens: List[int]
    prompt_len: int


class Engine:
    """Synchronous batched engine; one jitted prefill + one jitted decode.

    ``params`` may be a full-precision pytree or the output of
    ``repro.quant.quantize_params`` — ``QuantizedArray`` leaves flow through
    the jitted steps unchanged and are dequantized at their matmul sites
    (MoQ serving, paper §4: expert bytes shrink ~4x/8x with int8/int4).
    """

    def __init__(self, cfg: ModelConfig, params, ec: EngineConfig, *, memory=None,
                 prefix_embeds=None, obs=None):
        from repro.quant import prepare_params_for_serving
        from repro.serving.ep import MeshCall, init_engine_mesh, place_params

        # EP serving mesh (cfg.ep_mesh): resolve BEFORE cfg is captured by
        # the jit closures below — the mesh rewrites moe_impl to the
        # shard_map serving schedule (serving/ep.py, core/moe_serve.py).
        self._mesh, self._mesh_rules, cfg = init_engine_mesh(cfg)
        self.cfg = cfg

        if self._mesh is not None:
            from repro.parallel.sharding import use_mesh

            with use_mesh(self._mesh, self._mesh_rules):
                params = prepare_params_for_serving(cfg, params)
            params = place_params(self._mesh, self._mesh_rules, params)
            self.params = params
        else:
            self.params = params = prepare_params_for_serving(cfg, params)
        self.ec = ec
        self.memory = memory
        self.prefix_embeds = prefix_embeds
        capacity = ec.max_prefill + ec.max_decode + (
            cfg.frontend.n_tokens if (cfg.frontend is not None and cfg.family == "vlm") else 0
        )
        self._capacity = capacity
        cross_len = memory.shape[1] if memory is not None else 0

        from repro.obs import Obs

        # same default contract as ContinuousEngine: metrics on, tracer off,
        # routing collection off (it changes the decode step's signature)
        self.obs = obs if obs is not None else Obs()
        self._phase = self.obs.tracer.phase
        routing = self.obs.routing
        M = self.obs.metrics
        self._h_prefill = M.histogram("serve.batch_prefill_s")
        self._h_step = M.histogram("serve.decode_step_s", lo=1e-5, hi=10.0)
        self._c_decode_toks = M.counter("serve.decode_tokens", unit="tok")
        self._c_completed = M.counter("serve.requests_completed", unit="req")
        self._c_retraces = M.counter("serve.retraces", unit="compile")
        self._g_r_drop = M.gauge("routing.dropped_frac")
        self._g_r_ent = M.gauge("routing.entropy", unit="nat")
        self._g_r_imb = M.gauge("routing.imbalance")
        # per-layer routing summary of the most recent decode step
        # (summarize_routing dict) when obs.routing is on
        self.last_routing = None

        def _prefill(params, tokens, caches, memory, prefix_embeds):
            return prefill(cfg, params, tokens, caches, memory=memory, prefix_embeds=prefix_embeds)

        def _decode(params, token, index, caches, memory):
            # normalized 3-tuple return (routing = () when collection is
            # off): the call site can always rebind the donated caches in
            # one unpacking assignment — the shape the donation auditor
            # requires of every donating call
            out = decode_step(cfg, params, token, index, caches, memory=memory,
                              return_routing=routing)
            if routing:
                return out
            logits, caches = out
            return logits, caches, ()

        # caches are donated: the static engine re-allocates per batch, but
        # without donation every decode step double-buffers the KV cache
        self._prefill = jax.jit(_prefill, donate_argnums=(2,))
        self._decode = jax.jit(_decode, donate_argnums=(3,))
        self._cross_len = cross_len
        # Jit registry (same shape as ContinuousEngine's): name ->
        # (fn, donate_argnums, primary).  Both non-primary: the static engine
        # legitimately compiles once per batch shape (B, prompt length), so
        # the never-retrace-after-warmup contract belongs to
        # ContinuousEngine's fixed-shape tick only; compiles are still
        # counted into serve.retraces
        self._jit_registry = {"decode": (self._decode, (3,), False),
                              "prefill": (self._prefill, (2,), False)}
        if self._mesh is not None:
            # every entry point (execution, lower, eval_shape) runs under the
            # serving mesh; attribute forwarding keeps the watchdog's
            # _cache_size probe and the analysis gate working unchanged
            for _name in list(self._jit_registry):
                _fn, _don, _primary = self._jit_registry[_name]
                _w = MeshCall(_fn, self._mesh, self._mesh_rules)
                self._jit_registry[_name] = (_w, _don, _primary)
                setattr(self, "_" + _name, _w)
        for _name, (_fn, _don, _primary) in self._jit_registry.items():
            self.obs.watchdog.register(_name, _fn, aux=not _primary)

    def _make_caches(self, batch: int):
        return init_caches(
            self.cfg, batch, self._capacity,
            cross_len=self._cross_len, kv_bits=self.ec.kv_cache_bits,
        )

    # -- declared contracts for the static analysis suite ----------------
    def jitted_functions(self) -> dict:
        """name -> (jitted fn, donate_argnums, primary); see
        ContinuousEngine.jitted_functions."""
        return dict(self._jit_registry)

    def shape_contract(self) -> list:
        """Declared compile-shape contract: one signature per admissible
        (batch, prompt-length) pair, bounded by EngineConfig.  Neither
        function is primary — the static engine's compile count scales with
        distinct batch shapes by design (that is why ContinuousEngine
        exists); the contract still bounds the family and feeds the
        donation/trace checks."""
        from repro.analysis.contracts import ContractEntry

        aval = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        params = jax.tree.map(aval, self.params)
        mem = None if self.memory is None else aval(self.memory)
        pe = None if self.prefix_embeds is None else aval(self.prefix_embeds)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        ec = self.ec

        def caches_avals(b):
            return jax.eval_shape(lambda: self._make_caches(b))

        batches = sorted({1, 2, ec.max_batch} & set(range(1, ec.max_batch + 1))
                         | {ec.max_batch})
        lens = sorted({1, 16, ec.max_prefill} & set(range(1, ec.max_prefill + 1))
                      | {ec.max_prefill})
        _, don_p, prim_p = self._jit_registry["prefill"]
        _, don_d, prim_d = self._jit_registry["decode"]
        return [
            ContractEntry(
                name="prefill",
                fn=self._prefill,
                make=lambda b, s: (params, i32(b, s), caches_avals(b), mem, pe),
                points=tuple((b, s) for b in range(1, ec.max_batch + 1)
                             for s in range(1, ec.max_prefill + 1)),
                sample=tuple((b, s) for b in batches for s in lens),
                primary=prim_p, donate_argnums=don_p),
            ContractEntry(
                name="decode",
                fn=self._decode,
                make=lambda b: (params, i32(b, 1), i32(), caches_avals(b), mem),
                points=tuple((b,) for b in range(1, ec.max_batch + 1)),
                sample=tuple((b,) for b in batches),
                primary=prim_d, donate_argnums=don_d),
        ]

    def generate(self, requests: Sequence[Request], *, seed: int = 0) -> List[Response]:
        ec = self.ec
        out: List[Response] = []
        base = jax.random.PRNGKey(seed)
        for chunk, start in enumerate(range(0, len(requests), ec.max_batch)):
            # fold the chunk index into the key: chunk 2+ must not replay
            # chunk 1's sampling noise (chunk 0 keeps the unfolded key so
            # single-batch results are unchanged across versions)
            key = base if chunk == 0 else jax.random.fold_in(base, chunk)
            out.extend(self._generate_batch(requests[start : start + ec.max_batch], key))
        return out

    def _generate_batch(self, reqs: Sequence[Request], key: jax.Array) -> List[Response]:
        ec, cfg = self.ec, self.cfg
        B = len(reqs)
        # Right-align prompts into a fixed buffer so the last prefill position
        # is each row's final prompt token.
        S = min(max(len(r.prompt) for r in reqs), ec.max_prefill)
        toks = np.full((B, S), ec.pad_id, np.int32)
        for i, r in enumerate(reqs):
            p = list(r.prompt)[-S:]
            toks[i, S - len(p) :] = p

        caches = self._make_caches(B)
        t0 = time.perf_counter()
        with self._phase("engine.prefill"):
            logits, caches = self._prefill(
                self.params, jnp.asarray(toks), caches, self.memory, self.prefix_embeds
            )
            if self.obs.metrics.enabled or self.obs.tracer.enabled:
                with self._phase("engine.wait"):
                    # analysis: allow(block-sync) — deliberate timing fence for the prefill histogram
                    jax.block_until_ready(logits)
                self._h_prefill.observe(time.perf_counter() - t0)
        offset = (
            self.cfg.frontend.n_tokens if (cfg.frontend is not None and cfg.family == "vlm") else 0
        )

        max_new = min(max(r.max_new_tokens for r in reqs), ec.max_decode)
        generated = np.zeros((B, max_new), np.int32)
        done = np.zeros((B,), bool)
        cur = sample(logits, key, temperature=ec.temperature, top_k=ec.top_k, top_p=ec.top_p)
        t_prev = time.perf_counter()
        for t in range(max_new):
            with self._phase("engine.decode"):
                with self._phase("engine.wait"):
                    # analysis: allow(host-asarray) — THE per-step sync: tokens drive host-side eos/stop logic while the next step is dispatched
                    generated[:, t] = np.asarray(cur)  # blocks on the in-flight step
                now = time.perf_counter()
                if t:  # step t-1's device time ended at this sync point
                    self._h_step.observe(now - t_prev)
                t_prev = now
                self._c_decode_toks.inc(int((~done).sum()))
                done |= generated[:, t] == ec.eos_id
                if done.all():
                    generated = generated[:, : t + 1]
                    break
                key, sub = jax.random.split(key)
                idx = jnp.asarray(S + offset + t, jnp.int32)
                # single unpacking assignment: the donated caches are rebound
                # by the same statement that calls the donating function
                logits, caches, routing_tree = self._decode(
                    self.params, cur[:, None], idx, caches, self.memory
                )
                if self.obs.routing:
                    self.last_routing = summarize_routing(routing_tree) if routing_tree else None
                    if self.last_routing:
                        self._g_r_drop.set(self.last_routing["dropped_frac"])
                        self._g_r_ent.set(self.last_routing["entropy"])
                        self._g_r_imb.set(self.last_routing["imbalance"])
                fresh = self.obs.watchdog.tick()
                if fresh:
                    self._c_retraces.inc(fresh)
                cur = sample(logits, sub, temperature=ec.temperature, top_k=ec.top_k, top_p=ec.top_p)
        self._c_completed.inc(B)

        res = []
        for i, r in enumerate(reqs):
            g = generated[i].tolist()
            if ec.eos_id >= 0 and ec.eos_id in g:
                g = g[: g.index(ec.eos_id)]
            res.append(Response(tokens=g[: r.max_new_tokens], prompt_len=len(r.prompt)))
        return res
