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
"""
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


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _pool(s, quantized):
    """(kq, ks, vq, vs, kpos) shapes of one layer's page pool."""
    kd = jnp.int8 if quantized else jnp.bfloat16
    scales = s((PT, HKV, PAGE, 1), jnp.float32) if quantized else None
    return (s((PT, HKV, PAGE, DH), kd), scales, s((PT, HKV, PAGE, DH), kd), scales,
            s((PT, PAGE), jnp.int32))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, quantized):
    from repro.kernels.attention_paged import paged_decode_attention

    s = _sds(one_chip)
    q = s((SLOTS, HKV, H // HKV, DH), jnp.bfloat16)
    args = (q, *_pool(s, quantized), s((SLOTS, NT), jnp.int32), s((SLOTS, 1), jnp.int32))
    _assert_kernel(paged_decode_attention.lower(*args, scale=DH ** -0.5, interpret=False))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_chunk_prefill_compiles(one_chip, quantized):
    from repro.kernels.attention_prefill_paged import paged_prefill_attention

    s = _sds(one_chip)
    q = s((SLOTS, CHUNK, HKV, H // HKV, DH), jnp.bfloat16)
    chunk_kv = s((SLOTS, CHUNK, HKV, DH), jnp.bfloat16)
    args = (q, *_pool(s, quantized), s((SLOTS, NT), jnp.int32),
            s((SLOTS, CHUNK), jnp.int32), chunk_kv, chunk_kv)
    _assert_kernel(paged_prefill_attention.lower(*args, scale=DH ** -0.5, interpret=False))


@pytest.mark.parametrize("act", ["gelu", "swiglu"])
def test_grouped_mlp_compiles(one_chip, act):
    from repro.kernels.expert_mlp_grouped import grouped_mlp_kernel

    s = _sds(one_chip)
    up = s((E, D, F), jnp.bfloat16)
    wg = up if act == "swiglu" else None
    lowered = grouped_mlp_kernel.lower(
        s((CT, D), jnp.bfloat16), s((CT // 8,), jnp.int32), up, wg,
        s((E, F, D), jnp.bfloat16), act=act, interpret=False)
    _assert_kernel(lowered)


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
    _assert_kernel(lowered)


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
    kq, _, vq, _, kpos = _pool(_sds(rep), False)
    cache = {"k": kq, "v": vq, "pos": kpos}

    def decode(q, cache, row_pos, table):
        return attn._paged_decode_attend(q, cache, row_pos, table, AttnSpec(kind="global"),
                                         DH ** -0.5)

    with use_mesh(mesh, rules):
        lowered = jax.jit(decode).lower(
            jax.ShapeDtypeStruct((SLOTS, 1, H, DH), jnp.bfloat16, sharding=rows), cache,
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=rows),
            jax.ShapeDtypeStruct((SLOTS, NT), jnp.int32, sharding=rows))
    _assert_kernel(lowered)
