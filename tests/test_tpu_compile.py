"""Compile the serving main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler that ships with JAX compiles for a
described ``v5e:2x2`` topology and refuses what Mosaic would refuse on the
chip (block shapes off the (8, 128) tiling, scoped-VMEM overflow, kernels
the partitioner cannot split).  Shapes are ``nlg-350m-moe128``'s published
widths (d_model 1024, 16 kv-heads of 64, d_ff 4096, 128 experts) at the
serving settings ``chip_smoke.py`` drives: 8 slots, page_size 16, 256-token
prefill chunks, 1056-token capacity.  Nothing here runs or times anything.

The topology is described inside fixtures, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.

Each compiled kernel's custom call must also carry the name the benchmark's
trace reduction matches it by (``bench.trace.KERNELS``): a rename fails here
before it silences a per-layer metric on the chip.
"""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

D, H, HKV, DH, F, E = 1024, 16, 16, 64, 4096, 128
SLOTS, PAGE, CHUNK, CAPACITY = 8, 16, 256, 1024 + 32
NT = -(-CAPACITY // PAGE)  # block-table entries per slot
PT = SLOTS * NT + 1  # pool pages + the trash page
CT = (SLOTS + E * 7) // 8 * 8  # grouped rows of one decode tick (tile 8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # compiles for a described chip are written to a persistent cache that
    # a process without the chip cannot read back: keep them out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


CUSTOM_CALL = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target="tpu_custom_call"',
                         re.MULTILINE)


def _assert_kernel(lowered, kernel):
    """The program compiles to Mosaic custom calls, and each is named as
    the trace reduction's pattern for ``kernel`` expects."""
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.trace import KERNELS

    names = CUSTOM_CALL.findall(lowered.compile().as_text())
    assert names, "no tpu_custom_call in the compiled program"
    for name in names:
        assert KERNELS[kernel].match(name), (name, KERNELS[kernel].pattern)


def _pool(s, quantized, layers=()):
    """(kq, ks, vq, vs, kpos) shapes of one layer's page pool, or of a
    stack of ``layers`` pools given ``layers=(n,)``."""
    kd = jnp.int8 if quantized else jnp.bfloat16
    lead = tuple(layers) + (PT, PAGE)
    scales = s(lead + (HKV,), jnp.float32) if quantized else None
    return (s(lead + (HKV * DH,), kd), scales, s(lead + (HKV * DH,), kd), scales,
            s(lead, jnp.int32))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, quantized):
    from repro.kernels.attention_paged import paged_decode_attention

    s = _sds(one_chip)
    q = s((SLOTS, HKV, H // HKV, DH), jnp.bfloat16)
    args = (q, *_pool(s, quantized, (3,)), s((SLOTS, NT), jnp.int32), s((SLOTS, 1), jnp.int32),
            s((), jnp.int32))
    _assert_kernel(paged_decode_attention.lower(*args, scale=DH ** -0.5, interpret=False),
                   "paged_decode_attn")


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_chunk_prefill_compiles(one_chip, quantized):
    from repro.kernels.attention_prefill_paged import paged_prefill_attention

    s = _sds(one_chip)
    q = s((SLOTS, CHUNK, HKV, H // HKV, DH), jnp.bfloat16)
    chunk_kv = s((SLOTS, CHUNK, HKV, DH), jnp.bfloat16)
    args = (q, *_pool(s, quantized), s((SLOTS, NT), jnp.int32),
            s((SLOTS, CHUNK), jnp.int32), chunk_kv, chunk_kv)
    _assert_kernel(paged_prefill_attention.lower(*args, scale=DH ** -0.5, interpret=False),
                   "prefill_attn")


@pytest.mark.parametrize("act", ["gelu", "swiglu"])
def test_grouped_mlp_compiles(one_chip, act):
    from repro.kernels.expert_mlp_grouped import grouped_mlp_kernel

    s = _sds(one_chip)
    up = s((E, D, F), jnp.bfloat16)
    wg = up if act == "swiglu" else None
    lowered = grouped_mlp_kernel.lower(
        s((CT, D), jnp.bfloat16), s((CT // 8,), jnp.int32), up, wg,
        s((E, F, D), jnp.bfloat16), act=act, interpret=False)
    _assert_kernel(lowered, "grouped_mlp")


@pytest.mark.parametrize("bits", [8, 4])
def test_grouped_mlp_quant_compiles(one_chip, bits):
    from repro.kernels.expert_mlp_grouped import grouped_mlp_quant_kernel

    s = _sds(one_chip)
    pack = 2 if bits == 4 else 1
    lowered = grouped_mlp_quant_kernel.lower(
        s((CT, D), jnp.bfloat16), s((CT // 8,), jnp.int32),
        s((E, D // pack, F), jnp.int8), s((E, 1, F), jnp.float32), None, None,
        s((E, F // pack, D), jnp.int8), s((E, 1, D), jnp.float32),
        bits=bits, act="gelu", interpret=False)
    _assert_kernel(lowered, "grouped_mlp")


def test_paged_decode_under_ep_mesh_compiles(topo, monkeypatch):
    """Mosaic kernels are not partitioned automatically: under a 4-chip
    serving mesh the model's paged decode attention must run the kernel
    inside a shard_map over its slot shard (slots over the EP axis, the page
    pool replicated).  The kernel path is steered on here because the test
    process's backend is the CPU."""
    import repro.kernels.ops as ops
    from repro.configs.base import AttnSpec
    from repro.models import attention as attn
    from repro.parallel.sharding import DEFAULT_RULES, make_mesh, use_mesh

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(attn, "PAGED_BACKEND", ["kernel"])
    mesh = make_mesh((4,), ("data",), devices=topo.devices[:4])
    rules = {**DEFAULT_RULES, "expert": "data", "batch": "data"}
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    kq, _, vq, _, kpos = _pool(_sds(rep), False, (2,))
    cache = {"k": kq, "v": vq, "pos": kpos}

    def decode(q, cache, row_pos, table, layer):
        return attn._paged_decode_attend(q, cache, row_pos, table, layer,
                                         AttnSpec(kind="global"), DH ** -0.5)

    with use_mesh(mesh, rules):
        lowered = jax.jit(decode).lower(
            jax.ShapeDtypeStruct((SLOTS, 1, H, DH), jnp.bfloat16, sharding=rows), cache,
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=rows),
            jax.ShapeDtypeStruct((SLOTS, NT), jnp.int32, sharding=rows),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    _assert_kernel(lowered, "paged_decode_attn")


# An instruction of the compiled text: name, result shape, opcode.
INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]\S*\s+([\w\-]+)\(',
                   re.MULTILINE)
PARAM = re.compile(r'^\s*%?[\w.\-]+\s*=\s*(\w+)\[([\d,]*)\]\S*\s+parameter\((\d+)\)', re.MULTILINE)


def test_paged_decode_step_keeps_pool_in_place(one_chip, monkeypatch):
    """The whole paged decode step of a 2-segment stacked model (PR-MoE
    pattern at published widths, 2 repeats per segment) writes and reads
    each layer's pages in the stacked pools where they lie: no copy,
    dynamic-slice or dynamic-update-slice in the compiled program yields a
    K/V pool, a layer's or a stack's (only one layer's small ``[Pt, ps]``
    positions are sliced, for the kernel's position gather); every stacked
    pool parameter is aliased to its output; no temporary is as large as a
    layer's pool; and the kernel keeps the name the trace reduction
    matches."""
    import repro.core.moe as moe
    import repro.kernels.ops as ops
    from repro.core.prmoe import nlg_moe
    from repro.models import attention as attn
    from repro.models.model import init_paged_caches, init_params, paged_ragged_decode_step

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(attn, "PAGED_BACKEND", ["kernel"])
    monkeypatch.setattr(moe, "GROUPED_EXPERT_BACKEND", ["kernel"])
    cfg = nlg_moe("nlg-350m-prmoe-32-64-8l", 8, D, H, (32, 64), residual=True)
    assert [seg.repeats for seg in cfg.segments] == [2, 2]
    assert (cfg.num_kv_heads, cfg.head_dim) == (HKV, DH)
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    caches = place(jax.eval_shape(lambda: init_paged_caches(
        cfg, SLOTS, CAPACITY, n_pages=PT - 1, page_size=PAGE)))

    def step(params, tokens, positions, active, caches, tables):
        return paged_ragged_decode_step(cfg, params, tokens, positions, active, caches, tables,
                                        return_routed=True)

    s = _sds(one_chip)
    lowered = jax.jit(step, donate_argnums=(4,)).lower(
        params, s((SLOTS, 1), jnp.int32), s((SLOTS,), jnp.int32), s((SLOTS,), jnp.bool_), caches,
        s((SLOTS, NT), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()

    def elems(dims):
        n = 1
        for d in dims:
            n *= d
        return n

    moved = []
    for name, _, dims, op in INSTR.findall(text):
        dims = [int(d) for d in dims.split(",") if d]
        if op in ("copy", "dynamic-slice", "dynamic-update-slice") and PT in dims \
                and elems(dims) > PT * PAGE:
            moved.append(f"{op} {name} {dims}")
    assert not moved, moved

    header = text.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), \{", header)}
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    pools = [int(n) for _, dims, n in PARAM.findall(entry)
             if dims.split(",")[-3:] == [str(PT), str(PAGE), str(HKV * DH)]]
    assert len(pools) == 2 * 4, pools  # K and V of 2 positions x 2 segments
    assert set(pools) <= aliased, (pools, header[:400])

    layer_pool_bytes = 2 * PT * PAGE * HKV * DH * 2  # K + V, bf16
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool_bytes

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.trace import KERNELS

    names = CUSTOM_CALL.findall(text)
    assert any(KERNELS["paged_decode_attn"].match(n) for n in names), names
    assert all(any(p.match(n) for p in KERNELS.values()) for n in names), names
