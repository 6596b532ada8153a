"""Multi-device distribution tests.

These run in SUBPROCESSES with XLA_FLAGS=--xla_force_host_platform_device_count=8
because the main pytest process must keep the default single CPU device
(per the dry-run isolation requirement).  Each subprocess script asserts and
exits nonzero on failure.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.dist

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_script(body: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", body], capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"subprocess failed:\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    return r.stdout


PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.sharding import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
"""


class TestExpertParallel:
    def test_ep_matches_dense(self):
        run_script(PREAMBLE + """
from repro.configs.base import ModelConfig, FFNSpec
from repro.core.moe import init_moe, moe_layer
from repro.parallel.sharding import use_mesh

cfg = ModelConfig(name="t", family="moe", source="x", d_model=64, num_heads=4, num_kv_heads=2,
                  head_dim=16, vocab_size=100, segments=(), param_dtype="float32", compute_dtype="float32")
spec = FFNSpec(kind="moe", d_ff=128, num_experts=8, top_k=2, capacity_factor=8.0, residual=True)
p = init_moe(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))
y_ref, _ = moe_layer(cfg, spec, p, x, impl="dense")
with use_mesh(mesh):
    y_ep, _ = jax.jit(lambda p, x: moe_layer(cfg, spec, p, x, impl="ep"))(p, x)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep), atol=1e-4)

def loss(p, x, impl):
    y, a = moe_layer(cfg, spec, p, x, impl=impl)
    return jnp.sum(y**2) + 0.01*a
g_ref = jax.grad(loss)(p, x, "dense")
with use_mesh(mesh):
    g_ep = jax.jit(jax.grad(lambda p, x: loss(p, x, "ep")))(p, x)
jax.tree.map(lambda a,b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4), g_ref, g_ep)
print("EP OK")
""")

    def test_coordinated_a2a_group_size(self):
        """The §5.3 claim: a2a groups span only the EP axis (p/L), not p."""
        run_script(PREAMBLE + """
from repro.configs.base import ModelConfig, FFNSpec
from repro.core.moe import init_moe, moe_layer
from repro.parallel.sharding import use_mesh
import re

cfg = ModelConfig(name="t", family="moe", source="x", d_model=64, num_heads=4, num_kv_heads=2,
                  head_dim=16, vocab_size=100, segments=(), param_dtype="float32", compute_dtype="float32")
spec = FFNSpec(kind="moe", d_ff=128, num_experts=8, top_k=1, capacity_factor=4.0)
p = init_moe(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))
with use_mesh(mesh):
    txt = jax.jit(lambda p, x: moe_layer(cfg, spec, p, x, impl="ep")).lower(p, x).compile().as_text()
groups = []
for m in re.finditer(r'all-to-all[^\\n]*replica_groups=\\{\\{([^}]*)\\}', txt):
    groups.append(len(m.group(1).split(",")))
for m in re.finditer(r'all-to-all[^\\n]*replica_groups=\\[(\\d+),(\\d+)\\]', txt):
    groups.append(int(m.group(2)))
assert groups, "no all-to-all found in HLO"
assert all(g == 4 for g in groups), f"a2a groups {groups} != data-axis size 4 (coordinated a2a)"
print("coordinated a2a OK", groups)
""")


class TestHierarchicalA2A:
    def test_equals_flat_and_roundtrips(self):
        run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.collectives import (flat_all_to_all, flat_all_to_all_back,
    hierarchical_all_to_all, hierarchical_all_to_all_back)
from repro.parallel.sharding import make_mesh
from jax import shard_map
mesh = make_mesh((2, 4), ("pod", "data"))
E, C, D = 16, 4, 8
xg = jax.random.normal(jax.random.PRNGKey(0), (8, E, C, D))
def run(fn):
    def body(xs):
        return fn(xs.reshape(E, C, D))[None]
    return shard_map(body, mesh=mesh, in_specs=P(("pod","data"), None, None, None),
                     out_specs=P(("pod","data"), None, None, None))(xg)
flat = run(lambda x: flat_all_to_all(x, ("pod","data")))
hier = run(lambda x: hierarchical_all_to_all(x, "data", "pod"))
np.testing.assert_allclose(np.asarray(flat), np.asarray(hier), atol=0)
rt = run(lambda x: hierarchical_all_to_all_back(hierarchical_all_to_all(x, "data", "pod"), "data", "pod"))
np.testing.assert_allclose(np.asarray(rt), np.asarray(xg), atol=0)
print("hierarchical a2a OK")
""")


class TestShardedTrainStep:
    def test_train_step_on_mesh_matches_single_device(self):
        run_script(PREAMBLE + """
from repro.configs.registry import all_configs, make_reduced
from repro.models.model import init_params
from repro.training.optimizer import init_adamw
from repro.training.trainer import TrainConfig, make_train_step
from repro.parallel.sharding import use_mesh
from repro.parallel.params import param_pspecs, batch_pspec
from jax.sharding import NamedSharding

import dataclasses
cfg = make_reduced(all_configs()["llama4-maverick-400b-a17b"])
# The EP path sizes capacity per shard (T/ep tokens) while the single-device
# dense path sizes it over all T, so at the config's 1.25 they drop different
# tokens.  capacity_factor = num_experts is drop-free on both sides: only the
# arithmetic is compared.
def _drop_free(ls):
    if not getattr(ls.ffn, "num_experts", 0):
        return ls
    return dataclasses.replace(ls, ffn=dataclasses.replace(
        ls.ffn, capacity_factor=float(ls.ffn.num_experts)))
cfg = cfg.replace(segments=tuple(
    dataclasses.replace(seg, pattern=tuple(_drop_free(ls) for ls in seg.pattern))
    for seg in cfg.segments))
params = init_params(cfg, jax.random.PRNGKey(0))
opt = init_adamw(params)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)
step = make_train_step(cfg, TrainConfig(lr=1e-3, warmup_steps=1, decay_steps=10))
p1, o1, m1 = jax.jit(step)(params, opt, toks, toks)

with use_mesh(mesh):
    pspecs = param_pspecs(mesh, params, mode="train")
    shard = lambda t, s: jax.device_put(t, NamedSharding(mesh, s))
    params_s = jax.tree.map(shard, params, pspecs)
    opt_s = init_adamw(params_s)
    toks_s = jax.device_put(toks, NamedSharding(mesh, batch_pspec(mesh, 2)))
    # fresh wrapper: jax caches traces per function object, and the first
    # jax.jit(step) traced WITHOUT the mesh (dense-dispatch fallback baked
    # in); the mesh run must retrace so moe_impl='ep' sees the active mesh
    p2, o2, m2 = jax.jit(lambda *a: step(*a))(params_s, opt_s, toks_s, toks_s)
assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (float(m1["loss"]), float(m2["loss"]))
jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4),
             p1, p2)
print("sharded train step OK")
""")

    def test_decode_on_mesh_matches_single_device(self):
        run_script(PREAMBLE + """
from repro.configs.registry import all_configs, make_reduced
from repro.models.model import init_params, init_caches, prefill, decode_step
from repro.parallel.sharding import use_mesh
from repro.parallel.params import param_pspecs, cache_pspecs, batch_pspec
from jax.sharding import NamedSharding

cfg = make_reduced(all_configs()["gemma3-27b"])
params = init_params(cfg, jax.random.PRNGKey(0))
B, S = 8, 12
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S+1), 0, cfg.vocab_size)
caches = init_caches(cfg, B, capacity=S+2)
lg1, c1 = jax.jit(lambda p,t,c: prefill(cfg,p,t,c))(params, toks[:, :S], caches)
lg1d, _ = jax.jit(lambda p,t,i,c: decode_step(cfg,p,t,i,c))(params, toks[:, S:S+1], jnp.asarray(S, jnp.int32), c1)

with use_mesh(mesh):
    shard = lambda t, s: jax.device_put(t, NamedSharding(mesh, s))
    params_s = jax.tree.map(shard, params, param_pspecs(mesh, params))
    caches_s = jax.tree.map(shard, caches, cache_pspecs(mesh, caches, B))
    toks_s = jax.device_put(toks, NamedSharding(mesh, batch_pspec(mesh, 2)))
    lg2, c2 = jax.jit(lambda p,t,c: prefill(cfg,p,t,c))(params_s, toks_s[:, :S], caches_s)
    lg2d, _ = jax.jit(lambda p,t,i,c: decode_step(cfg,p,t,i,c))(params_s, toks_s[:, S:S+1], jnp.asarray(S, jnp.int32), c2)
np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2), atol=2e-4)
np.testing.assert_allclose(np.asarray(lg1d), np.asarray(lg2d), atol=2e-4)
print("sharded decode OK")
""")


class TestAllGatherEPSchedule:
    def test_decode_regime_matches_dense(self):
        """Small-batch (decode) EP schedule: all-gather tokens -> local
        experts -> psum_scatter (EXPERIMENTS.md §Perf P3 iteration 1)."""
        run_script(PREAMBLE + """
from repro.configs.base import ModelConfig, FFNSpec
from repro.core.moe import init_moe, moe_layer
from repro.parallel.sharding import use_mesh

cfg = ModelConfig(name="t", family="moe", source="x", d_model=64, num_heads=4, num_kv_heads=2,
                  head_dim=16, vocab_size=100, segments=(), param_dtype="float32", compute_dtype="float32")
spec = FFNSpec(kind="moe", d_ff=128, num_experts=8, top_k=2, capacity_factor=8.0, residual=True)
p = init_moe(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 64))  # 1 token/shard -> allgather path
y_ref, a_ref = moe_layer(cfg, spec, p, x, impl="dense")
with use_mesh(mesh):
    y_ep, a_ep = jax.jit(lambda p, x: moe_layer(cfg, spec, p, x, impl="ep"))(p, x)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep), atol=1e-4)
assert abs(float(a_ref) - float(a_ep)) < 1e-5
print("allgather EP OK")
""")


class TestContextParallelAttention:
    def test_nondivisible_heads_seq_sharded_matches(self):
        """llama4-style head counts (not divisible by 'model') fall back to
        query-sequence sharding; results must match the unsharded reference
        (EXPERIMENTS.md §Perf P2 iteration 1)."""
        run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel.sharding import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
from repro.configs.base import AttnSpec, ModelConfig
from repro.models.attention import attention, init_attention
from repro.parallel.sharding import use_mesh

cfg = ModelConfig(name="t", family="dense", source="x", d_model=64, num_heads=6, num_kv_heads=2,
                  head_dim=16, vocab_size=64, segments=(), param_dtype="float32", compute_dtype="float32")
assert cfg.num_heads % 4 != 0  # triggers the context-parallel fallback
spec = AttnSpec(kind="global")
ap = init_attention(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
pos = jnp.arange(16, dtype=jnp.int32)[None]
y_ref, _ = attention(cfg, spec, ap, x, pos, mode="train")
with use_mesh(mesh):
    y_cp, _ = jax.jit(lambda ap, x: attention(cfg, spec, ap, x, pos, mode="train"))(ap, x)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_cp), atol=1e-4)
print("context-parallel attention OK")
""")


class TestCrossPodHierarchicalEP:
    def test_hier_ep_matches_dense(self):
        """Experts sharded over (pod, data) with the paper's Fig. 8
        hierarchical two-stage a2a; values and grads must match the
        single-device dense reference."""
        run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import ModelConfig, FFNSpec
from repro.core.moe import init_moe, moe_layer
from repro.core.moe_parallel import set_ep_pod
from repro.parallel.sharding import use_mesh, RULESETS

cfg = ModelConfig(name="t", family="moe", source="x", d_model=64, num_heads=4, num_kv_heads=2,
                  head_dim=16, vocab_size=100, segments=(), param_dtype="float32", compute_dtype="float32")
from repro.parallel.sharding import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
spec = FFNSpec(kind="moe", d_ff=128, num_experts=8, top_k=2, capacity_factor=8.0)
p = init_moe(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))
y_ref, _ = moe_layer(cfg, spec, p, x, impl="dense")
set_ep_pod(True)
with use_mesh(mesh, RULESETS["ep_pod"]):
    y_ep, _ = jax.jit(lambda p, x: moe_layer(cfg, spec, p, x, impl="ep"))(p, x)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep), atol=1e-4)

def loss(p, x, impl):
    y, a = moe_layer(cfg, spec, p, x, impl=impl)
    return jnp.sum(y**2) + 0.01*a
g_ref = jax.grad(loss)(p, x, "dense")
with use_mesh(mesh, RULESETS["ep_pod"]):
    g_ep = jax.jit(jax.grad(lambda p, x: loss(p, x, "ep")))(p, x)
jax.tree.map(lambda a,b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4), g_ref, g_ep)
print("cross-pod hierarchical EP OK")
""")
