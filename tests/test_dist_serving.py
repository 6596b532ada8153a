"""Multi-device expert-parallel SERVING tests (dist marker).

Parity tier: a sharded ``ContinuousEngine``/``Engine`` (``cfg.ep_mesh``)
must produce greedy decode output token-IDENTICAL to the single-device
engine — across arch mixes (glm4 attention-only, gemma3 sliding-window +
int8 KV, the paper's NLG MoE), mesh shapes (8,), (4, 2), (2, 4) (the 2-d
shapes take the hierarchical two-hop all-to-all), the grouped dropless
kernel, batched multi-slot prefill, and prefix sharing.  Exactness is by
construction: the EP schedules reconstruct the reference kernels'
arithmetic (global gating + all_gather/psum of expert outputs, or a
trailing-padded a2a with drop-free capacity), so the assertion is ``==``
on token lists, not allclose.

Invariant tier: property-fuzzed (tests/_hyp.py shim) routing/collective
conservation — after the all-to-all exchange no token is duplicated or
dropped under skewed routing, per-device received counts sum to the global
dispatch, hierarchical == flat — plus preemption/resume on a sharded
engine draining the page pool, and the ``moe_impl="dense"`` multi-device
guard regression.

Like tests/test_dist.py, everything runs in SUBPROCESSES under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps its single CPU device.
"""
import os
import subprocess
import sys

import pytest

from tests._hyp import given, settings, st

pytestmark = pytest.mark.dist

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_script(body: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", body], capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"subprocess failed:\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    return r.stdout


# prompts mix: two sharing an 8-token prefix (page-aligned at page_size=8),
# one long (chunked prefill), one single-token
ENGINE_PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_default_matmul_precision", "highest")
from repro.configs.registry import all_configs, make_reduced, with_moe_ffn
from repro.models.model import init_params
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Engine, EngineConfig, Request

def serve(cfg, params, prompts, n_new, **kw):
    eng = ContinuousEngine(cfg, params, **kw)
    ids = [eng.submit(Request(prompt=p, max_new_tokens=n_new)) for p in prompts]
    done = eng.run_until_done()
    return [done[i].tokens for i in ids], eng

PRE = [7, 7, 3, 5, 1, 2, 9, 4]
PROMPTS = [PRE + [3, 5, 1], PRE + [8, 2], [11, 2, 3, 7, 5, 6, 1, 9, 2, 3], [5]]
"""


class TestShardedEngineParity:
    def test_glm4_flat_mesh(self):
        """Dense arch on (8,): attention/KV data-parallel over slots, weights
        replicated — the no-MoE degenerate case of the serving mesh."""
        run_script(ENGINE_PREAMBLE + """
cfg = make_reduced(all_configs()["glm4-9b"])
params = init_params(cfg, jax.random.PRNGKey(0))
kw = dict(slots=4, capacity=64, paged=True, page_size=8)
base, _ = serve(cfg, params, PROMPTS, 8, **kw)
ep, _ = serve(cfg.replace(ep_mesh=(8,)), params, PROMPTS, 8, **kw)
assert base == ep, (base, ep)
print("glm4 (8,) OK")
""")

    def test_nlg_moe_hier_mesh(self):
        """The paper's NLG MoE on (4, 2): experts sharded over both axes, the
        chunked-prefill dense kernel goes through the hierarchical two-hop
        a2a, decode through the replicated-token all_gather schedule.
        capacity_factor=8.0 gives the a2a schedule drop-free headroom (the
        parity-by-construction precondition for the token-sharded path)."""
        run_script(ENGINE_PREAMBLE + """
cfg = with_moe_ffn(make_reduced(all_configs()["nlg-350m-moe128"]),
                   num_experts=8, capacity_factor=8.0)
params = init_params(cfg, jax.random.PRNGKey(0))
kw = dict(slots=4, capacity=64, paged=True, page_size=8)
base, _ = serve(cfg, params, PROMPTS, 8, **kw)
ep, eng = serve(cfg.replace(ep_mesh=(4, 2)), params, PROMPTS, 8, **kw)
assert eng.cfg.moe_impl == "ep_serve", eng.cfg.moe_impl
assert base == ep, (base, ep)
print("nlg (4,2) OK")
""")

    def test_gemma3_int8_kv(self):
        """Arch mix + quantized KV: gemma3 (sliding-window/global interleave)
        with int8 KV cache blocks, sharded over (4, 2)."""
        run_script(ENGINE_PREAMBLE + """
cfg = make_reduced(all_configs()["gemma3-27b"])
params = init_params(cfg, jax.random.PRNGKey(0))
kw = dict(slots=4, capacity=64, paged=True, page_size=8, kv_cache_bits=8)
base, _ = serve(cfg, params, PROMPTS, 8, **kw)
ep, _ = serve(cfg.replace(ep_mesh=(4, 2)), params, PROMPTS, 8, **kw)
assert base == ep, (base, ep)
print("gemma3 int8 (4,2) OK")
""")

    def test_nlg_grouped_batched_prefix(self):
        """Composition: grouped (dropless) expert kernel per device + batched
        multi-slot prefill + prefix sharing, experts over (2, 4)."""
        run_script(ENGINE_PREAMBLE + """
cfg = with_moe_ffn(make_reduced(all_configs()["nlg-350m-moe128"]), num_experts=8)
cfg = cfg.replace(moe_impl="grouped")
params = init_params(cfg, jax.random.PRNGKey(0))
kw = dict(slots=4, capacity=64, paged=True, page_size=8,
          prefix_sharing=True, prefill_mode="batched")
base, _ = serve(cfg, params, PROMPTS, 8, **kw)
ep, eng = serve(cfg.replace(ep_mesh=(2, 4)), params, PROMPTS, 8, **kw)
assert eng.cfg.moe_impl == "ep_grouped", eng.cfg.moe_impl
assert base == ep, (base, ep)
print("nlg grouped batched prefix (2,4) OK")
""")

    def test_static_engine(self):
        """The static (non-continuous) Engine over (8,): same placement and
        shard_map wrapping, contiguous caches instead of paged."""
        run_script(ENGINE_PREAMBLE + """
cfg = with_moe_ffn(make_reduced(all_configs()["nlg-350m-moe128"]),
                   num_experts=8, capacity_factor=8.0)
params = init_params(cfg, jax.random.PRNGKey(0))
ec = EngineConfig(max_batch=4, max_prefill=32, max_decode=8)
reqs = [Request(prompt=p, max_new_tokens=8) for p in PROMPTS]
base = [r.tokens for r in Engine(cfg, params, ec).generate(reqs)]
ep = [r.tokens for r in Engine(cfg.replace(ep_mesh=(8,)), params, ec).generate(reqs)]
assert base == ep, (base, ep)
print("static Engine (8,) OK")
""")


class TestPreemptionDrain:
    def test_sharded_pool_drains_after_preemption(self):
        """Page-pressure preemption + resume on a SHARDED engine: the host
        scheduler must stay mesh-agnostic (identical preemption decisions and
        token output as single-device), and after completion every per-shard
        page is back on the freelist (extends the test_kv_pool_prop.py drain
        invariant to the sharded engine)."""
        run_script(ENGINE_PREAMBLE + """
cfg = with_moe_ffn(make_reduced(all_configs()["nlg-350m-moe128"]),
                   num_experts=8, capacity_factor=8.0)
params = init_params(cfg, jax.random.PRNGKey(0))
# 10 pages cannot hold 4 slots' prompt+decode footprint -> forced preemption
kw = dict(slots=4, capacity=32, paged=True, page_size=4, n_pages=10)
base, ref = serve(cfg, params, PROMPTS, 8, **kw)
ep, eng = serve(cfg.replace(ep_mesh=(4, 2)), params, PROMPTS, 8, **kw)
assert eng.preemptions > 0, "workload did not exercise preemption"
assert eng.preemptions == ref.preemptions, (eng.preemptions, ref.preemptions)
assert base == ep, (base, ep)
eng.pool.check()
assert eng.pool.free_count == eng.n_pages, (eng.pool.free_count, eng.n_pages)
assert eng.pool.used_count == 0
print("preempt/drain OK", eng.preemptions)
""")


class TestMoEDenseGuard:
    def test_dense_impl_raises_under_multi_device_mesh(self):
        """Regression for the documented XLA SPMD hazard: the GSPMD-partitioned
        dense scatter/gather dispatch miscomputes under a >1-device mesh, so
        requesting it there must raise an informative error instead of
        silently serving wrong numbers (single-device use stays fine)."""
        run_script("""
import jax, jax.numpy as jnp
from repro.configs.base import FFNSpec
from repro.core.moe import init_moe, moe_layer
from repro.serving.ep import build_serving_mesh
from repro.parallel.sharding import use_mesh

class C:
    d_model = 32
    moe_impl = "dense"

spec = FFNSpec(kind="moe", d_ff=64, num_experts=8, top_k=2, capacity_factor=2.0)
p = init_moe(jax.random.PRNGKey(0), C, spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 32), jnp.float32)
moe_layer(C, spec, p, x, impl="dense")  # no mesh: allowed
mesh, rules = build_serving_mesh((4, 2))
with use_mesh(mesh, rules):
    try:
        moe_layer(C, spec, p, x, impl="dense")
    except ValueError as e:
        assert "numerically unsafe" in str(e), str(e)
    else:
        raise AssertionError("dense dispatch under a multi-device mesh did not raise")
print("dense guard OK")
""")


# ---------------------------------------------------------------------------
# Property fuzz: routing / collective conservation invariants
# ---------------------------------------------------------------------------

# Per-shard gating is replayed on the HOST (no mesh) — identical arithmetic —
# then the dispatch buffers go through the real shard_map all-to-all; every
# invariant is checked against the host replay.  Token payloads carry their
# global id in channel 0 and a count of 1.0 in channel 1.
_A2A_FUZZ = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.gating import top_k_gating
from repro.core.dispatch import dispatch_dense
from repro.parallel.collectives import flat_all_to_all, hierarchical_all_to_all
from repro.parallel.sharding import make_mesh
from jax import shard_map

SEED = %d
rng = np.random.default_rng(SEED)
E, K, T_loc = 8, 2, 8
CAP = T_loc * K  # >= worst-case per-shard skew: zero drops by construction

for trial in range(4):
    for shape, names in [((8,), ("data",)), ((4, 2), ("pod", "data"))]:
        mesh = make_mesh(shape, names)
        n_dev = int(np.prod(shape))
        E_loc = E // n_dev
        # skewed routing: 1-2 hot experts soak up most of the probability
        hot = rng.choice(E, size=int(rng.integers(1, 3)), replace=False)
        logits = rng.normal(size=(n_dev, T_loc, E)).astype(np.float32)
        logits[..., hot] += 4.0
        gs = [top_k_gating(jnp.asarray(logits[r]), K, CAP) for r in range(n_dev)]
        assert all(bool(jnp.all(g.keep)) for g in gs), "capacity headroom violated"
        bufs = []
        for r, g in enumerate(gs):
            ids = jnp.arange(T_loc, dtype=jnp.float32) + 1 + r * T_loc  # 1-based
            x = jnp.stack([ids, jnp.ones_like(ids)], axis=-1)  # [T_loc, 2]
            bufs.append(dispatch_dense(x, g, CAP, E))
        xg = jnp.stack(bufs)  # [n_dev, E, CAP, 2]
        spec = P(names, None, None, None)
        def run(fn):
            body = lambda xs: fn(xs.reshape(E, CAP, 2))[None]
            return shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)(xg)
        flat = np.asarray(run(lambda b: flat_all_to_all(b, names)))
        if len(names) == 2:
            hier = np.asarray(run(lambda b: hierarchical_all_to_all(b, names[1], names[0])))
            assert np.array_equal(flat, hier), "hierarchical a2a != flat a2a"
        # flat[r]: [E_loc, n_dev*CAP, 2] = device r's received expert rows
        counts = flat[..., 1]
        ids = flat[..., 0]
        assert set(np.unique(counts)) <= {0.0, 1.0}
        # (1) per-device received counts == host-replayed routing to its experts,
        #     and they sum to the global dispatch total
        eidx = np.stack([np.asarray(g.expert_idx) for g in gs])  # [n_dev, T_loc, K]
        for r in range(n_dev):
            lo = r * E_loc
            expect = int(((eidx >= lo) & (eidx < lo + E_loc)).sum())
            got = int(counts[r].sum())
            assert got == expect, (r, got, expect)
        assert int(counts.sum()) == n_dev * T_loc * K
        # (2) no token duplicated or dropped: every global id arrives exactly K times
        arrived = ids[counts > 0].astype(np.int64)
        want = np.repeat(np.arange(1, n_dev * T_loc + 1), K)
        assert np.array_equal(np.sort(arrived), want), "token multiset mismatch"
        # (3) expert ownership: rows land only in their owner's local buffer
        for r in range(n_dev):
            for e_loc in range(E_loc):
                e = r * E_loc + e_loc
                expect_ids = sorted(
                    int(t + 1 + s * T_loc)
                    for s in range(n_dev) for t in range(T_loc) for k in range(K)
                    if eidx[s, t, k] == e)
                got_ids = sorted(ids[r, e_loc][counts[r, e_loc] > 0].astype(np.int64).tolist())
                assert got_ids == expect_ids, (e, got_ids, expect_ids)
print("a2a conservation OK")
"""


class TestRoutingInvariants:
    @settings(max_examples=3, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000))
    def test_a2a_token_conservation(self, seed):
        """Skewed-routing fuzz over (8,) and (4, 2) meshes: per-device counts
        after the all-to-all sum to the global dispatch, no token duplicated
        or dropped, expert rows land only on the owning device, hierarchical
        two-hop identical to flat."""
        run_script(_A2A_FUZZ % seed)

    @settings(max_examples=2, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000))
    def test_hier_roundtrip_random_buffers(self, seed):
        """hierarchical a2a then its inverse is the identity on random
        buffers, and matches flat, for both 2-d mesh factorizations."""
        run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.collectives import (flat_all_to_all, flat_all_to_all_back,
    hierarchical_all_to_all, hierarchical_all_to_all_back)
from repro.parallel.sharding import make_mesh
from jax import shard_map

rng = np.random.default_rng(%d)
for shape in [(2, 4), (4, 2)]:
    mesh = make_mesh(shape, ("pod", "data"))
    E = 8 * int(rng.integers(1, 3))
    C, D = int(rng.integers(1, 5)), int(rng.integers(1, 9))
    xg = jnp.asarray(rng.normal(size=(8, E, C, D)).astype(np.float32))
    spec = P(("pod", "data"), None, None, None)
    def run(fn):
        body = lambda xs: fn(xs.reshape(E, C, D))[None]
        return shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)(xg)
    flat = run(lambda x: flat_all_to_all(x, ("pod", "data")))
    hier = run(lambda x: hierarchical_all_to_all(x, "data", "pod"))
    assert np.array_equal(np.asarray(flat), np.asarray(hier))
    rt = run(lambda x: hierarchical_all_to_all_back(
        hierarchical_all_to_all(x, "data", "pod"), "data", "pod"))
    assert np.array_equal(np.asarray(rt), np.asarray(xg))
    rtf = run(lambda x: flat_all_to_all_back(flat_all_to_all(x, ("pod", "data")), ("pod", "data")))
    assert np.array_equal(np.asarray(rtf), np.asarray(xg))
print("hier roundtrip OK")
""" % seed)


# ---------------------------------------------------------------------------
# Speculative decoding under an EP mesh: refuse loudly, never miscompute
# ---------------------------------------------------------------------------


class TestSpecUnderEPMesh:
    def test_spec_draft_raises_clear_not_implemented(self):
        """Speculation's CoW fork plan is host-side per slot while the EP
        mesh places the page pool per rank — until the verify pass is
        taught to shard, arming both together must raise a clear
        NotImplementedError at engine construction (NOT silently serve
        wrong tokens or crash mid-tick)."""
        run_script("""
import jax
from repro.configs.registry import all_configs, make_reduced
from repro.models.model import init_params
from repro.serving.continuous import ContinuousEngine

cfg = make_reduced(all_configs()["nlg-350m-moe128"]).replace(ep_mesh=(4,))
params = init_params(cfg.replace(ep_mesh=()), jax.random.PRNGKey(0))
try:
    ContinuousEngine(cfg, params, slots=2, capacity=32, paged=True,
                     page_size=4, spec_draft=(cfg.replace(ep_mesh=()), params))
except NotImplementedError as e:
    msg = str(e)
    assert "expert-parallel" in msg and "spec" in msg, msg
    print("spec+EP refused OK")
else:
    raise AssertionError("spec_draft over an EP mesh must refuse")
""", n_dev=4)
