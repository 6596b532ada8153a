"""Paged KV-cache block pool + scheduler: allocator invariants, the Pallas
page-gather kernel vs its einsum ref, model-level paged-vs-contiguous
parity, scheduler behavior (fragmentation, preemption round-trip, page
reuse, free-block admission), and the scheduler-bug regressions fixed in
the same PR (prompt-truncation clamp, per-chunk PRNG folding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import Segment
from repro.configs.registry import all_configs, make_reduced
from repro.models.model import (
    init_caches,
    init_paged_caches,
    init_params,
    paged_prefill_into_slot,
    paged_ragged_decode_step,
    prefill_into_slot,
    ragged_decode_step,
)
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Engine, EngineConfig, Request
from repro.serving.kv_pool import BlockTables, KVBlockPool


@pytest.fixture(scope="module")
def setup():
    cfg = make_reduced(all_configs()["glm4-9b"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Host-side allocator
# ---------------------------------------------------------------------------


class TestBlockPool:
    def test_alloc_free_reuse(self):
        pool = KVBlockPool(4, 8)
        a = pool.alloc(3, owner=0)
        assert len(a) == 3 and pool.free_count == 1
        pool.free(a[:2])
        assert pool.free_count == 3
        b = pool.alloc(3, owner=1)
        assert len(b) == 3 and pool.free_count == 0
        # freed pages were recycled, not duplicated
        assert len(set(b) | set(a[2:])) == 4

    def test_alloc_all_or_nothing(self):
        pool = KVBlockPool(4, 8)
        assert pool.alloc(5, owner=0) is None
        assert pool.free_count == 4  # nothing was handed out
        assert pool.alloc(4, owner=0) is not None

    def test_double_free_raises(self):
        pool = KVBlockPool(4, 8)
        a = pool.alloc(2, owner=0)
        pool.free(a)
        with pytest.raises(ValueError, match="double free"):
            pool.free(a[:1])

    def test_release_owner_is_preemption_safe(self):
        pool = KVBlockPool(8, 4)
        pool.alloc(3, owner=0)
        pool.alloc(2, owner=1)
        assert len(pool.release(0)) == 3
        assert pool.free_count == 6
        assert pool.release(0) == []  # stale release frees nothing
        assert pool.release(7) == []  # unknown owner is a no-op

    def test_accounting(self):
        pool = KVBlockPool(10, 16)
        assert pool.pages_for(0) == 0
        assert pool.pages_for(1) == 1
        assert pool.pages_for(16) == 1
        assert pool.pages_for(17) == 2
        pool.alloc(5, owner=2)
        assert pool.used_count == 5 and pool.occupancy == 0.5
        assert sorted(pool.owned_by(2)) == sorted(pool.owned_by(2))


class TestBlockTables:
    def test_append_reset(self):
        bt = BlockTables(2, 4)
        bt.append(0, [7, 3])
        assert bt.n_mapped(0) == 2 and bt.n_mapped(1) == 0
        bt.append(0, [1])
        assert list(bt.row(0)[:3]) == [7, 3, 1]
        bt.reset(0)
        assert bt.n_mapped(0) == 0

    def test_overflow_raises(self):
        bt = BlockTables(1, 2)
        bt.append(0, [0, 1])
        with pytest.raises(ValueError, match="overflow"):
            bt.append(0, [2])


# ---------------------------------------------------------------------------
# Pallas page-gather kernel vs einsum ref
# ---------------------------------------------------------------------------


N_LAYERS = 3  # pools stacked per segment position: [layers, Pt, ...]


def _toy_pool(quantized, layer=1):
    """A stack of ``N_LAYERS`` page pools with different contents per layer,
    and the kernel's arguments reading layer ``layer`` of it."""
    key = jax.random.PRNGKey(0)
    B, Hkv, G, dh, ps, nt, Pt = 3, 2, 2, 8, 4, 5, 12  # Pt-1 = trash page
    L = N_LAYERS
    q = jax.random.normal(key, (B, Hkv, G, dh), jnp.float32)
    # pool pages are token rows, every head side by side: [L, Pt, ps, Hkv*dh]
    kf = jax.random.normal(jax.random.fold_in(key, 1), (L, Pt, ps, Hkv * dh), jnp.float32)
    vf = jax.random.normal(jax.random.fold_in(key, 2), (L, Pt, ps, Hkv * dh), jnp.float32)
    kpos = np.full((L, Pt, ps), -1, np.int32)
    tables = np.full((B, nt), -1, np.int32)
    seqs = {0: ([3, 7, 0], 10), 1: ([5, 9], 6), 2: ([1], 2)}
    for b, (pages, n) in seqs.items():
        tables[b, : len(pages)] = pages
        for t in range(n):
            kpos[:, pages[t // ps], t % ps] = t
    tbl = jnp.asarray(np.where(tables < 0, Pt - 1, tables), jnp.int32)
    qpos = jnp.asarray([[seqs[b][1] - 1] for b in range(B)], jnp.int32)
    if quantized:
        from repro.quant.kv import kv_quantize_values

        rows = lambda a: a.reshape(L, Pt, ps, -1)
        kq, ks = map(rows, kv_quantize_values(kf.reshape(L, Pt, ps, Hkv, dh)))
        vq, vs = map(rows, kv_quantize_values(vf.reshape(L, Pt, ps, Hkv, dh)))
    else:
        kq, ks, vq, vs = kf, None, vf, None
    return q, kq, ks, vq, vs, jnp.asarray(kpos), tbl, qpos, jnp.asarray(layer, jnp.int32)


def _one_layer(args, layer):
    """The same call on layer ``layer``'s pool alone: a stack of one."""
    pick = lambda a: None if a is None else a[layer:layer + 1]
    q, kq, ks, vq, vs, kpos, tbl, qpos, _ = args
    return (q, pick(kq), pick(ks), pick(vq), pick(vs), pick(kpos), tbl, qpos,
            jnp.asarray(0, jnp.int32))


class TestPagedKernel:
    @pytest.mark.parametrize("quantized", [False, True])
    def test_kernel_matches_ref(self, quantized):
        from repro.kernels.attention_paged import (
            paged_decode_attention,
            paged_decode_attention_ref,
        )

        args = _toy_pool(quantized)
        out_k = paged_decode_attention(*args, scale=0.35, interpret=True)
        out_r = paged_decode_attention_ref(*args, scale=0.35)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)

    def test_kernel_window_softcap(self):
        from repro.kernels.attention_paged import (
            paged_decode_attention,
            paged_decode_attention_ref,
        )

        args = _toy_pool(False)
        kw = dict(scale=0.35, causal=True, window=3, softcap=5.0)
        out_k = paged_decode_attention(*args, interpret=True, **kw)
        out_r = paged_decode_attention_ref(*args, **kw)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)

    def test_unmapped_entries_contribute_nothing(self):
        """Shrinking a row's mapped pages must equal zero-padding: -1 table
        entries (clamped to the trash page) are fully masked."""
        from repro.kernels.attention_paged import paged_decode_attention_ref

        q, kq, ks, vq, vs, kpos, tbl, qpos, layer = _toy_pool(False)
        out = paged_decode_attention_ref(q, kq, ks, vq, vs, kpos, tbl, qpos, layer, scale=0.35)
        # row 2 uses 1 page; widen its view to 5 (all trash beyond page 0)
        assert np.isfinite(np.asarray(out)).all()
        out2 = paged_decode_attention_ref(
            q, kq, ks, vq, vs, kpos, tbl.at[2, 1:].set(kq.shape[1] - 1), qpos, layer,
            scale=0.35,
        )
        np.testing.assert_allclose(np.asarray(out[2]), np.asarray(out2[2]), atol=1e-6)

    @pytest.mark.parametrize("layer", [0, N_LAYERS - 1], ids=["first", "last"])
    @pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
    def test_layer_indexed_reads_its_layer(self, quantized, layer):
        """The kernel reads layer ``layer`` of the stacked pools in place:
        it matches the ref on the stack, and equals the call on that layer's
        pool alone, with and without a window and a softcap; other layers'
        contents (which differ) change nothing."""
        from repro.kernels.attention_paged import (
            paged_decode_attention,
            paged_decode_attention_ref,
        )

        args = _toy_pool(quantized, layer)
        for kw in (dict(scale=0.35), dict(scale=0.35, window=3, softcap=5.0)):
            out_k = np.asarray(paged_decode_attention(*args, interpret=True, **kw))
            out_r = np.asarray(paged_decode_attention_ref(*args, **kw))
            np.testing.assert_allclose(out_k, out_r, atol=1e-5)
            alone = np.asarray(paged_decode_attention(*_one_layer(args, layer),
                                                      interpret=True, **kw))
            np.testing.assert_array_equal(out_k, alone)
            other = (layer + 1) % N_LAYERS
            out_o = np.asarray(paged_decode_attention(*args[:-1], jnp.asarray(other),
                                                      interpret=True, **kw))
            assert np.abs(out_o - out_k).max() > 1e-3  # the layers differ


# ---------------------------------------------------------------------------
# Model-level parity: paged vs contiguous caches
# ---------------------------------------------------------------------------


class TestPagedModelParity:
    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_staggered_decode_matches_contiguous(self, setup, kv_bits):
        """Two requests at different positions, admitted via page-scatter
        prefill and ragged-decoded through block tables, must produce the
        same logits as the contiguous slot-pool path."""
        cfg, params = setup
        cap, ps = 20, 4
        p0, p1 = [3, 5, 7, 9, 11], [2, 4, 6]

        contig = init_caches(cfg, 2, cap, kv_bits=kv_bits)
        paged = init_paged_caches(cfg, 2, cap, n_pages=10, page_size=ps, kv_bits=kv_bits)
        pool = KVBlockPool(10, ps)
        tables = BlockTables(2, -(-cap // ps))
        for i, p in enumerate((p0, p1)):
            toks = jnp.asarray([p], jnp.int32)
            pos = jnp.arange(len(p), dtype=jnp.int32)[None]
            slot = jnp.asarray(i, jnp.int32)
            _, contig = prefill_into_slot(cfg, params, toks, pos, slot, contig)
            tables.append(i, pool.alloc(pool.pages_for(len(p)), owner=i))
            _, paged = paged_prefill_into_slot(
                cfg, params, toks, pos, slot, paged, jnp.asarray(tables.row(i)),
                capacity=cap, kv_bits=kv_bits,
            )
        toks = jnp.asarray([[1], [1]], jnp.int32)
        positions = jnp.asarray([len(p0), len(p1)], jnp.int32)
        active = jnp.ones((2,), bool)
        # grow tables for the decode write position
        for i, p in enumerate((p0, p1)):
            if tables.n_mapped(i) <= len(p) // ps:
                tables.append(i, pool.alloc(1, owner=i))
        lg_c, _ = ragged_decode_step(cfg, params, toks, positions, active, contig)
        lg_p, _ = paged_ragged_decode_step(
            cfg, params, toks, positions, active, paged, jnp.asarray(tables.table)
        )
        np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_p), atol=2e-4)


# ---------------------------------------------------------------------------
# The decode step writes the stacked pools in place
# ---------------------------------------------------------------------------


def _multi_repeat_cfg():
    """Reduced PR-MoE (a dense and a MoE layer per pattern, two segments)
    with 2 repeats per segment: each segment position's pools stack 2
    layers, so the step's writes and reads go by layer index."""
    cfg = make_reduced(all_configs()["nlg-350m-prmoe-32-64"])
    return cfg.replace(segments=tuple(Segment(seg.pattern, 2) for seg in cfg.segments))


class TestInPlaceDecodeWrite:
    def test_step_writes_only_active_rows_entries(self):
        """One decode step over stacked pools full of random contents: each
        active row's K/V/pos lands at ``[layer, page, offset]`` of every
        layer (K/V equal to what the contiguous step writes for that row and
        position), the inactive row's writes land only in the trash page
        with ``pos == -1``, and every other entry of every layer's pool is
        bit-identical."""
        cfg = _multi_repeat_cfg()
        params = init_params(cfg, jax.random.PRNGKey(1))
        ps, n_pages, slots, cap = 4, 12, 3, 16
        Pt, nt = n_pages + 1, cap // ps
        Hkv, dh = cfg.num_kv_heads, cfg.head_dim
        rng = np.random.default_rng(0)
        # row 0 holds 5 tokens (pages 3, 7), row 1 is inactive, row 2 holds 2 (page 1)
        tables = np.full((slots, nt), -1, np.int32)
        tables[0, :2], tables[2, :1] = [3, 7], [1]
        lengths = {0: 5, 2: 2}  # the step writes each active row at position == length
        paged = init_paged_caches(cfg, slots, cap, n_pages=n_pages, page_size=ps)
        contig = init_caches(cfg, slots, cap)
        for sk in paged:
            for pk in paged[sk]:
                R = paged[sk][pk]["self"]["k"].shape[0]
                pool = {n: rng.standard_normal((R, Pt, ps, Hkv * dh)).astype(np.float32)
                        for n in ("k", "v")}
                pool["pos"] = np.full((R, Pt, ps), -1, np.int32)
                ring = {n: np.zeros((R, slots, cap, Hkv, dh), np.float32) for n in ("k", "v")}
                ring["pos"] = np.full((R, slots, cap), -1, np.int32)
                for b, n in lengths.items():
                    for t in range(n):
                        page, off = tables[b, t // ps], t % ps
                        pool["pos"][:, page, off] = ring["pos"][:, b, t] = t
                        for name in ("k", "v"):
                            ring[name][:, b, t] = pool[name][:, page, off].reshape(R, Hkv, dh)
                paged[sk][pk]["self"] = {n: jnp.asarray(a) for n, a in pool.items()}
                contig[sk][pk]["self"] = {n: jnp.asarray(a) for n, a in ring.items()}
        toks = jnp.asarray([[3], [5], [7]], jnp.int32)
        positions = jnp.asarray([lengths[0], 0, lengths[2]], jnp.int32)
        active = jnp.asarray([True, False, True])
        lg_p, new = jax.jit(lambda c: paged_ragged_decode_step(
            cfg, params, toks, positions, active, c, jnp.asarray(tables)))(paged)
        lg_c, cnew = ragged_decode_step(cfg, params, toks, positions, active, contig)
        np.testing.assert_allclose(np.asarray(lg_p)[[0, 2]], np.asarray(lg_c)[[0, 2]], atol=2e-4)

        written = {b: (tables[b, n // ps], n % ps) for b, n in lengths.items()}
        for sk in paged:
            for pk in paged[sk]:
                old = {n: np.asarray(a) for n, a in paged[sk][pk]["self"].items()}
                got = {n: np.asarray(a) for n, a in new[sk][pk]["self"].items()}
                want = {n: np.asarray(a) for n, a in cnew[sk][pk]["self"].items()}
                untouched = np.ones(old["pos"].shape, bool)  # [R, Pt, ps]
                untouched[:, Pt - 1] = False  # the trash page
                for b, (page, off) in written.items():
                    untouched[:, page, off] = False
                    n = lengths[b]
                    np.testing.assert_array_equal(got["pos"][:, page, off], n)
                    for name in ("k", "v"):
                        np.testing.assert_allclose(
                            got[name][:, page, off],
                            want[name][:, b, n].reshape(-1, Hkv * dh), atol=1e-4)
                for name in ("k", "v", "pos"):
                    np.testing.assert_array_equal(got[name][untouched], old[name][untouched])
                np.testing.assert_array_equal(got["pos"][:, Pt - 1], -1)

    def test_multi_repeat_greedy_matches_contiguous(self):
        """20 greedy decode ticks over the stacked pools of a 2-segment,
        2-repeat model give the contiguous engine's tokens."""
        cfg = _multi_repeat_cfg()
        params = init_params(cfg, jax.random.PRNGKey(1))
        prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [4, 4, 2, 1, 6, 6]]
        want, _ = _serve(cfg, params, prompts, 20, slots=3, capacity=32)
        got, eng = _serve(cfg, params, prompts, 20, slots=3, capacity=32,
                          paged=True, page_size=4, n_pages=24)
        assert got == want, (got, want)
        assert eng.pool.free_count == eng.n_pages


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


def _serve(cfg, params, prompts, n_new, **kw):
    eng = ContinuousEngine(cfg, params, **kw)
    ids = [eng.submit(Request(prompt=p, max_new_tokens=n_new)) for p in prompts]
    done = eng.run_until_done()
    return [done[i].tokens for i in ids], eng


class TestPagedEngine:
    @pytest.mark.parametrize("prefix", [False, True])
    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_matches_contiguous_greedy(self, setup, kv_bits, prefix):
        """Acceptance: identical greedy tokens, paged vs contiguous, fp and
        int8 KV — and unchanged when prefix sharing rides along (prompts 1/3
        share a full page, exercising the CoW path against the contiguous
        oracle too)."""
        cfg, params = setup
        # 3 slots: prompts 1 and 3 are in flight together, so the shared
        # [1,2,3,4] page is still live (and indexed) at prompt 3's admission
        prompts = [[1, 2, 3, 4], [9, 8, 7], [1, 2, 3, 4, 5]]
        want, _ = _serve(cfg, params, prompts, 5, slots=3, capacity=32,
                         kv_cache_bits=kv_bits)
        got, eng = _serve(cfg, params, prompts, 5, slots=3, capacity=32,
                          kv_cache_bits=kv_bits, paged=True, page_size=4,
                          n_pages=24, prefix_sharing=prefix)
        assert got == want, (got, want)
        assert eng.pool.free_count == eng.n_pages  # everything returned
        if prefix:
            assert eng.prefix_hits >= 1  # [1,2,3,4] page re-used by prompt 3

    @pytest.mark.parametrize("prefix", [False, True])
    def test_window_arch_mixes_rings_and_pages(self, prefix):
        """Sliding-window layers keep per-slot rings while global layers
        page — parity must hold on a local+global arch (gemma3), with and
        without prefix sharing of the global-layer pages."""
        cfg = make_reduced(all_configs()["gemma3-27b"])  # window 8 reduced
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompts = [[1, 2, 3, 4, 5, 6], [9, 8, 7], [1, 2, 3, 4, 9]]
        want, _ = _serve(cfg, params, prompts, 6, slots=3, capacity=24)
        got, eng = _serve(cfg, params, prompts, 6, slots=3, capacity=24,
                          paged=True, page_size=4, n_pages=18,
                          prefix_sharing=prefix)
        assert got == want, (got, want)
        if prefix:
            assert eng.prefix_hits >= 1

    @pytest.mark.parametrize("prefill_mode", ["chunked", "scatter"])
    def test_fragmentation_many_short_one_long(self, setup, prefill_mode):
        """The paged pool serves many short requests plus one long one from
        HALF the contiguous reservation (slots*capacity would need 64 pages'
        worth; the pool holds 20) — the fragmentation win, token-exact.
        Parametrized over the admission path so the retained scatter oracle
        keeps scheduler coverage too."""
        cfg, params = setup
        prompts = [[7, 7, 7] for _ in range(6)] + [[1, 2, 3, 4, 5, 6, 7, 8]]
        n_new = [3] * 6 + [20]
        want_eng = ContinuousEngine(cfg, params, slots=4, capacity=32)
        got_eng = ContinuousEngine(cfg, params, slots=4, capacity=32,
                                   paged=True, page_size=2, n_pages=20,
                                   prefill_mode=prefill_mode)
        outs = []
        for eng in (want_eng, got_eng):
            ids = [eng.submit(Request(prompt=p, max_new_tokens=n))
                   for p, n in zip(prompts, n_new)]
            done = eng.run_until_done()
            outs.append([done[i].tokens for i in ids])
        assert outs[0] == outs[1]
        assert got_eng.pool.free_count == 20

    @pytest.mark.parametrize("prefill_mode", ["chunked", "scatter"])
    def test_preemption_round_trip(self, setup, prefill_mode):
        """A pool too small for all admitted sequences preempts the youngest
        slot back to the queue; resumed decoding is token-exact.  Runs under
        both admission paths — preemption + re-admission is exactly where
        the scatter oracle's temp-prefill machinery could rot unseen."""
        cfg, params = setup
        prompts = [[i + 1] * 6 for i in range(3)]
        want, _ = _serve(cfg, params, prompts, 8, slots=3, capacity=32,
                         paged=True, page_size=4, n_pages=64,
                         prefill_mode=prefill_mode)
        got, eng = _serve(cfg, params, prompts, 8, slots=3, capacity=32,
                          paged=True, page_size=4, n_pages=8,
                          prefill_mode=prefill_mode)
        assert eng.preemptions >= 1
        assert got == want, (got, want)

    def test_page_pressure_batched_readmission(self, setup):
        """Regression: a completion that unblocks a queued request mid-tick
        must not feed the freshly admitted slot a token sampled from its
        pre-admission (inactive-row) logits.  4 long prompts through 2 slots
        with a pool that forces preemption and staggered re-admission must
        match each request served alone."""
        cfg, params = setup
        prompts = [[10 + i] * 40 for i in range(4)]
        solo = []
        for p in prompts:
            got, _ = _serve(cfg, params, [p], 8, slots=1, capacity=64,
                            paged=True, page_size=4, n_pages=16)
            solo.append(got[0])
        got, eng = _serve(cfg, params, prompts, 8, slots=2, capacity=64,
                          paged=True, page_size=4, n_pages=20)
        assert eng.preemptions >= 1
        assert got == solo, (got, solo)

    def test_double_preemption_resumes_exactly(self, setup):
        """Regression: preempting the SAME request twice must not duplicate
        its generated prefix in the rebuilt context (prompt and generated are
        re-queued separately, not as a fused context)."""
        cfg, params = setup
        p = [1, 2, 3, 4, 5, 6]
        want, _ = _serve(cfg, params, [p], 10, slots=1, capacity=32,
                         paged=True, page_size=4)
        eng = ContinuousEngine(cfg, params, slots=1, capacity=32,
                               paged=True, page_size=4)
        rid = eng.submit(Request(prompt=p, max_new_tokens=10))
        eng.step(); eng.step()
        eng._preempt(0)          # kick it back to the queue mid-flight
        eng.step(); eng.step()   # re-admitted, decodes a little more
        eng._preempt(0)          # and again
        done = eng.run_until_done()
        assert eng.preemptions == 2
        assert done[rid].tokens == want[0], (done[rid].tokens, want[0])

    def test_admission_by_free_block_count(self, setup):
        """A free slot alone is not enough: the second request waits in the
        queue until the first request's pages come back."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, slots=2, capacity=16,
                               paged=True, page_size=4, n_pages=4)
        eng.submit(Request(prompt=list(range(1, 13)), max_new_tokens=4))
        eng.submit(Request(prompt=list(range(20, 32)), max_new_tokens=4))
        # both slots are free, but a 12-token prompt takes 3 of 4 pool pages,
        # so the second request cannot be admitted yet
        assert sum(s.active for s in eng.slots) == 1
        assert len(eng.queue) == 1
        done = eng.run_until_done()
        assert len(done) == 2 and all(len(r.tokens) == 4 for r in done.values())

    def test_page_reuse_is_clean(self, setup):
        """Regression: recycled pages must not leak the previous occupant's
        K/V (stale pos entries).  Back-to-back traffic through one engine
        must match a fresh engine per request."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, slots=1, capacity=32,
                               paged=True, page_size=4, n_pages=8)
        outs = []
        for p in ([1, 2, 3, 4, 5, 6, 7], [9, 9, 8, 8, 7]):
            rid = eng.submit(Request(prompt=p, max_new_tokens=6))
            outs.append(eng.run_until_done()[rid].tokens)
        for p, got in zip(([1, 2, 3, 4, 5, 6, 7], [9, 9, 8, 8, 7]), outs):
            want, _ = _serve(cfg, params, [p], 6, slots=1, capacity=32,
                             paged=True, page_size=4, n_pages=8)
            assert got == want[0], (p, got, want[0])

    def test_step_metrics_surface(self, setup):
        cfg, params = setup
        _, eng = _serve(cfg, params, [[1, 2, 3]], 3, slots=2, capacity=16,
                        paged=True, page_size=4)
        assert eng.metrics_log, "step() should record per-tick metrics"
        m = eng.last_metrics
        for key in ("tick", "active_slots", "queue_depth", "tok_per_s",
                    "free_pages", "page_occupancy", "preemptions"):
            assert key in m, key
        assert m["free_pages"] == eng.n_pages


class TestSchedulerRegressions:
    def test_admit_truncation_clamps_budget(self, setup):
        """Regression: max_new_tokens >= capacity used to flip the truncation
        index positive and keep the WRONG end of the prompt.  The clamped
        request must behave exactly like its explicit equivalent (last
        context token, capacity-1 budget)."""
        cfg, params = setup
        prompt = list(range(100, 112))  # 12 tokens, capacity 8
        got, _ = _serve(cfg, params, [prompt], 20, slots=1, capacity=8)
        assert len(got[0]) == 7  # budget clamped to capacity - 1
        want, _ = _serve(cfg, params, [prompt[-1:]], 7, slots=1, capacity=8)
        assert got[0] == want[0], (got[0], want[0])

    def test_admit_truncation_keeps_prompt_tail(self, setup):
        """When only part of the prompt fits, the kept part is the LAST
        (newest) tokens."""
        cfg, params = setup
        prompt = [11, 12, 13, 14, 15, 16]
        got, _ = _serve(cfg, params, [prompt], 4, slots=1, capacity=8)
        want, _ = _serve(cfg, params, [prompt[-4:]], 4, slots=1, capacity=8)
        assert got[0] == want[0]

    def test_engine_chunks_do_not_replay_sampling_noise(self, setup):
        """Regression: Engine.generate reused the identical PRNG key for
        every max_batch chunk, so chunk 2+ replayed chunk 1's noise."""
        cfg, params = setup
        ec = EngineConfig(max_batch=1, max_prefill=16, max_decode=12,
                          temperature=1.0)
        eng = Engine(cfg, params, ec)
        reqs = [Request(prompt=[1, 2, 3], max_new_tokens=12) for _ in range(2)]
        out = eng.generate(reqs, seed=0)
        assert out[0].tokens != out[1].tokens
        # chunk 0 must still follow the unfolded key: identical to a
        # single-request call (back-compat with pre-fix sampling streams)
        solo = eng.generate(reqs[:1], seed=0)
        assert out[0].tokens == solo[0].tokens
