"""Chunked prefill-into-pages: the direct-write admission path that replaced
the temp-contiguous-then-scatter prefill (PR 3/4).  Locks in

  * the Pallas prefill-chunk kernel vs its gather-and-concat einsum ref
    (fp + int8 pages, window/softcap, the empty-pool first chunk whose tiles
    are fully masked, and the recompute-overlap masking that keeps a
    shared-prefix key from being counted twice);
  * model-level parity: chunk-by-chunk ``paged_prefill_chunk`` vs the
    one-shot scatter oracle ``paged_prefill_into_slot`` — same logits, same
    subsequent decode, across chunk-boundary edge cases;
  * engine-level greedy parity: ``prefill_mode="chunked"`` (default) vs
    ``prefill_mode="scatter"`` — token-identical across fp and int8 KV,
    glm4 (fully paged) + gemma3 (window-ring mix) + recurrentgemma (LRU
    resume), with prefix sharing on and off, and a prompt-length sweep +/- 1
    around page multiples;
  * the admission state machine: no temp contiguous buffer anywhere in the
    chunked path, long-prompt admissions never stall running decodes for
    more than one chunk budget per tick, mid-prefill preemption resumes
    token-exact, fork admissions wait for a mid-prefill base instead of
    degrading, and shared prefixes skip their prefill FLOPs on fully-paged
    archs (and only there)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import all_configs, make_reduced
from repro.models.model import (
    arch_fully_paged,
    init_paged_caches,
    init_params,
    paged_prefill_chunk,
    paged_prefill_into_slot,
    paged_ragged_decode_step,
)
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Request
from repro.serving.kv_pool import BlockTables, KVBlockPool

PRE = [7, 7, 3, 5, 1, 2, 9, 4]  # 2 full pages at page_size=4 — shared preamble


@pytest.fixture(scope="module")
def setup():
    cfg = make_reduced(all_configs()["glm4-9b"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def setup_gemma():
    cfg = make_reduced(all_configs()["gemma3-27b"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _serve(cfg, params, prompts, n_new, **kw):
    eng = ContinuousEngine(cfg, params, **kw)
    ids = [eng.submit(Request(prompt=p, max_new_tokens=n_new)) for p in prompts]
    done = eng.run_until_done()
    return [done[i].tokens for i in ids], eng


# ---------------------------------------------------------------------------
# Pallas prefill-chunk kernel vs einsum ref
# ---------------------------------------------------------------------------


def _toy_chunk(quantized, n_hist=6):
    key = jax.random.PRNGKey(0)
    C, Hkv, G, dh, ps, Pt = 5, 2, 2, 8, 4, 10
    q = jax.random.normal(key, (C, Hkv, G, dh), jnp.float32)
    kf = jax.random.normal(jax.random.fold_in(key, 1), (Pt, Hkv, ps, dh), jnp.float32)
    vf = jax.random.normal(jax.random.fold_in(key, 2), (Pt, Hkv, ps, dh), jnp.float32)
    ck = jax.random.normal(jax.random.fold_in(key, 3), (C, Hkv, dh), jnp.float32)
    cv = jax.random.normal(jax.random.fold_in(key, 4), (C, Hkv, dh), jnp.float32)
    kpos = np.full((Pt, ps), -1, np.int32)
    hist_pages = [3, 7]
    for t in range(n_hist):
        kpos[hist_pages[t // ps], t % ps] = t
    table = np.array([3, 7, 1, Pt - 1], np.int32)  # page 1 fresh, last unmapped
    qpos = jnp.arange(n_hist, n_hist + C, dtype=jnp.int32)
    if quantized:
        from repro.quant.kv import kv_quantize_values

        kq, ks = kv_quantize_values(kf)
        vq, vs = kv_quantize_values(vf)
    else:
        kq, ks, vq, vs = kf, None, vf, None
    return q, kq, ks, vq, vs, jnp.asarray(kpos), jnp.asarray(table), qpos, ck, cv


def _rows(q, kq, ks, vq, vs, kpos, table, qpos, ck, cv):
    """One slot's chunk as a one-row batch (the kernel's grid axis 0)."""
    return q[None], kq, ks, vq, vs, kpos, table[None], qpos[None], ck[None], cv[None]


class TestPrefillKernel:
    @pytest.mark.parametrize("quantized", [False, True])
    def test_kernel_matches_ref(self, quantized):
        from repro.kernels.attention_prefill_paged import (
            paged_prefill_attention,
            paged_prefill_attention_ref,
        )

        args = _rows(*_toy_chunk(quantized))
        out_k = paged_prefill_attention(*args, scale=0.3, interpret=True)
        out_r = paged_prefill_attention_ref(*args, scale=0.3)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)

    def test_kernel_window_softcap(self):
        from repro.kernels.attention_prefill_paged import (
            paged_prefill_attention,
            paged_prefill_attention_ref,
        )

        args = _rows(*_toy_chunk(False))
        kw = dict(scale=0.3, causal=True, window=4, softcap=5.0)
        out_k = paged_prefill_attention(*args, interpret=True, **kw)
        out_r = paged_prefill_attention_ref(*args, **kw)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)

    def test_empty_pool_first_chunk(self):
        """The first chunk of an unshared admission sees only fully-masked
        page tiles before its own in-flight tile; the masked-tile guard must
        keep them out of the softmax normalizer (finite, ref-equal output)."""
        from repro.kernels.attention_prefill_paged import (
            paged_prefill_attention,
            paged_prefill_attention_ref,
        )

        q, kq, ks, vq, vs, _, table, _, ck, cv = _toy_chunk(False)
        kpos = jnp.full((kq.shape[0], kq.shape[2]), -1, jnp.int32)  # [Pt, ps]
        qpos = jnp.arange(q.shape[0], dtype=jnp.int32)
        args = _rows(q, kq, ks, vq, vs, kpos, table, qpos, ck, cv)
        out_k = paged_prefill_attention(*args, scale=0.3, interpret=True)
        out_r = paged_prefill_attention_ref(*args, scale=0.3)
        assert np.isfinite(np.asarray(out_k)).all()
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)

    def test_recompute_overlap_counts_keys_once(self):
        """When a shared-prefix admission recomputes the prefix (ring/SSM
        archs), the chunk's positions are live in the pool AND in flight.
        Pool keys at positions >= the chunk start must be masked: the result
        equals attending with those pool entries absent."""
        from repro.kernels.attention_prefill_paged import (
            paged_prefill_attention,
            paged_prefill_attention_ref,
        )

        q, kq, ks, vq, vs, kpos, table, _, ck, cv = _toy_chunk(False, n_hist=6)
        qpos = jnp.arange(2, 2 + q.shape[0], dtype=jnp.int32)  # overlaps hist 2..5
        full = _rows(q, kq, ks, vq, vs, kpos, table, qpos, ck, cv)
        # oracle: the same pool with the overlapping entries truly emptied
        kpos_clean = jnp.where(kpos >= 2, -1, kpos)
        clean = _rows(q, kq, ks, vq, vs, kpos_clean, table, qpos, ck, cv)
        out_k = paged_prefill_attention(*full, scale=0.3, interpret=True)
        out_r = paged_prefill_attention_ref(*clean, scale=0.3)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)

    @pytest.mark.parametrize("quantized", [False, True])
    def test_rows_are_independent(self, quantized):
        """One launch over several rows equals one launch per row: each row
        reads only its own table and masks history at its own chunk start;
        a row of all -1 positions (an inactive slot) stays finite."""
        from repro.kernels.attention_prefill_paged import paged_prefill_attention

        q, kq, ks, vq, vs, kpos, table, qpos, ck, cv = _toy_chunk(quantized)
        C = q.shape[0]
        tables = jnp.stack([table, jnp.asarray([7, 1, 3, kq.shape[0] - 1], jnp.int32), table])
        qposs = jnp.stack([qpos, jnp.arange(2, 2 + C, dtype=jnp.int32),
                           jnp.full((C,), -1, jnp.int32)])
        qs = jnp.stack([q, q[::-1], q])
        cks, cvs = jnp.stack([ck, cv, ck]), jnp.stack([cv, ck, cv])
        both = paged_prefill_attention(qs, kq, ks, vq, vs, kpos, tables, qposs, cks, cvs,
                                       scale=0.3, interpret=True)
        assert np.isfinite(np.asarray(both)).all()
        for b in range(2):
            one = paged_prefill_attention(
                qs[b:b + 1], kq, ks, vq, vs, kpos, tables[b:b + 1], qposs[b:b + 1],
                cks[b:b + 1], cvs[b:b + 1], scale=0.3, interpret=True)
            np.testing.assert_allclose(np.asarray(both[b]), np.asarray(one[0]), atol=1e-6)


# ---------------------------------------------------------------------------
# Model level: chunk-by-chunk direct write vs the scatter oracle
# ---------------------------------------------------------------------------


class TestModelChunkParity:
    @pytest.mark.parametrize("kv_bits", [0, 8])
    @pytest.mark.parametrize("arch", ["glm4-9b", "gemma3-27b"])
    def test_chunked_matches_scatter(self, arch, kv_bits):
        """Chunked direct-write prefill must reproduce the scatter path's
        last-token logits and subsequent decode (fp: ~exact; int8: within
        quantization noise of reading earlier chunks back dequantized)."""
        cfg = make_reduced(all_configs()[arch])
        params = init_params(cfg, jax.random.PRNGKey(0))
        cap, ps, n_pages = 24, 4, 10
        prompt = [3, 5, 7, 9, 11, 2, 4, 6, 8, 1]  # 10 tokens, 3 pages

        def admit(chunks):
            caches = init_paged_caches(cfg, 2, cap, n_pages=n_pages, page_size=ps,
                                       kv_bits=kv_bits)
            pool = KVBlockPool(n_pages, ps)
            tables = BlockTables(2, -(-cap // ps))
            tables.append(0, pool.alloc(pool.pages_for(len(prompt)), owner=0))
            row = jnp.asarray(tables.row(0))
            if chunks is None:  # scatter oracle
                lg, caches = paged_prefill_into_slot(
                    cfg, params, jnp.asarray([prompt], jnp.int32),
                    jnp.arange(len(prompt), dtype=jnp.int32)[None],
                    jnp.asarray(0, jnp.int32), caches, row,
                    capacity=cap, kv_bits=kv_bits)
            else:
                for j, (s, e) in enumerate(chunks):
                    lg, caches = paged_prefill_chunk(
                        cfg, params, jnp.asarray([prompt[s:e]], jnp.int32),
                        jnp.arange(s, e, dtype=jnp.int32)[None],
                        jnp.asarray(0, jnp.int32), caches, row,
                        capacity=cap, kv_bits=kv_bits, page_size=ps,
                        reset=(j == 0))
            tables.append(0, pool.alloc(1, owner=0))
            tk = jnp.asarray([[1], [1]], jnp.int32)
            posd = jnp.asarray([len(prompt), 0], jnp.int32)
            act = jnp.asarray([True, False])
            ld, _ = paged_ragged_decode_step(cfg, params, tk, posd, act, caches,
                                             jnp.asarray(tables.table))
            return np.asarray(lg), np.asarray(ld[0])

        lg_s, ld_s = admit(None)
        for split in ([(0, 10)], [(0, 4), (4, 8), (8, 10)], [(0, 8), (8, 10)]):
            lg_c, ld_c = admit(split)
            atol = 1e-4 if kv_bits == 0 else 0.05
            np.testing.assert_allclose(lg_c, lg_s, atol=atol)
            np.testing.assert_allclose(ld_c, ld_s, atol=atol)
            assert np.argmax(lg_c) == np.argmax(lg_s)
            assert np.argmax(ld_c) == np.argmax(ld_s)


# ---------------------------------------------------------------------------
# Engine level: greedy parity chunked vs scatter
# ---------------------------------------------------------------------------


class TestEngineParity:
    @pytest.mark.parametrize("prefix", [False, True])
    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_matches_scatter_greedy(self, setup, kv_bits, prefix):
        """Acceptance: token-identical greedy outputs, chunked (multi-chunk
        forced by a small budget) vs the PR 3/4 scatter path — fp and int8
        KV, prefix sharing on and off."""
        cfg, params = setup
        prompts = [PRE + [11], PRE + [12, 13], [9, 8, 7], PRE + [14, 15, 16]]
        kw = dict(slots=3, capacity=32, kv_cache_bits=kv_bits, paged=True,
                  page_size=4, n_pages=24, prefix_sharing=prefix)
        want, _ = _serve(cfg, params, prompts, 5, prefill_mode="scatter", **kw)
        got, eng = _serve(cfg, params, prompts, 5, prefill_mode="chunked",
                          prefill_chunk=4, **kw)
        assert got == want, (got, want)
        assert eng.pool.free_count == eng.n_pages
        if prefix:
            assert eng.prefix_hits >= 1

    @pytest.mark.parametrize("prefix", [False, True])
    def test_window_ring_mix_gemma3(self, setup_gemma, prefix):
        """Window rings advance chunk-by-chunk while global layers write
        pages directly; a shared prefix is recomputed (rings must be rebuilt)
        but its pages are still shared and never written."""
        cfg, params = setup_gemma
        assert not arch_fully_paged(cfg)
        prompts = [PRE + [11, 12], PRE + [13], [1, 2, 3]]
        kw = dict(slots=2, capacity=24, paged=True, page_size=4, n_pages=12,
                  prefix_sharing=prefix)
        want, _ = _serve(cfg, params, prompts, 6, prefill_mode="scatter", **kw)
        got, eng = _serve(cfg, params, prompts, 6, prefill_mode="chunked",
                          prefill_chunk=4, **kw)
        assert got == want, (got, want)
        if prefix:
            assert eng.prefix_hits >= 1
            assert eng.prefill_tokens_skipped == 0  # rings force the recompute

    def test_ring_size_chunk_starting_mid_ring(self, setup_gemma):
        """Regression: a chunk of EXACTLY ring size landing at a position
        that is not a ring multiple (prompt 20, chunk 12 -> final chunk
        [12:20) of size 8 == window at offset 12 % 8 = 4) must scatter at
        pos % cap, not rebuild at index 0 — the rebuild layout breaks the
        ring invariant slot == pos % cap and evicts the wrong tokens on the
        next decode write."""
        cfg, params = setup_gemma
        prompt = [(5 * i) % 89 + 1 for i in range(20)]
        kw = dict(slots=1, capacity=32, paged=True, page_size=4, n_pages=8)
        want, _ = _serve(cfg, params, [prompt], 8, prefill_mode="scatter", **kw)
        got, _ = _serve(cfg, params, [prompt], 8, prefill_mode="chunked",
                        prefill_chunk=12, **kw)
        assert got == want, (got, want)

    def test_lru_resume_recurrentgemma(self):
        """RG-LRU recurrence + conv prefix resume across chunks (hybrid arch
        with local-attention rings and no paged layers at all)."""
        cfg = make_reduced(all_configs()["recurrentgemma-2b"])
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [11, 12, 13]]
        kw = dict(slots=2, capacity=24, paged=True, page_size=4, n_pages=12)
        want, _ = _serve(cfg, params, prompts, 5, prefill_mode="scatter", **kw)
        got, _ = _serve(cfg, params, prompts, 5, prefill_mode="chunked",
                        prefill_chunk=4, **kw)
        assert got == want, (got, want)

    def test_slot_reuse_resets_recurrent_state(self):
        """Regression: the FIRST chunk of an admission must reset the slot's
        per-slot leaves — the row still holds the previous occupant's
        SSM/LRU recurrence and conv prefix, and `prefill_chunk` mode resumes
        from the cache (the scatter path rewrote the whole row implicitly).
        Back-to-back traffic through one slot must match fresh serving."""
        cfg = make_reduced(all_configs()["recurrentgemma-2b"])
        params = init_params(cfg, jax.random.PRNGKey(0))
        kw = dict(slots=1, capacity=24, paged=True, page_size=4, n_pages=12)
        eng = ContinuousEngine(cfg, params, prefill_chunk=4, **kw)
        outs = []
        for p in ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [11, 12, 13]):
            rid = eng.submit(Request(prompt=p, max_new_tokens=5))
            outs.append(eng.run_until_done()[rid].tokens)
        for p, got in zip(([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [11, 12, 13]), outs):
            want, _ = _serve(cfg, params, [p], 5, prefill_mode="scatter", **kw)
            assert got == want[0], (p, got, want[0])

    def test_slot_reuse_resets_window_ring(self, setup_gemma):
        """Same regression for window rings: the second occupant is SHORTER
        than the window, so the previous occupant's stale ring entries (at
        positions <= the new queries') would survive the causal mask if the
        first chunk resumed instead of resetting."""
        cfg, params = setup_gemma
        kw = dict(slots=1, capacity=24, paged=True, page_size=4, n_pages=12)
        eng = ContinuousEngine(cfg, params, prefill_chunk=4, **kw)
        prompts = ([21, 22, 23, 24, 25, 26, 27, 28, 29, 30], [31, 32, 33])
        outs = []
        for p in prompts:
            rid = eng.submit(Request(prompt=p, max_new_tokens=5))
            outs.append(eng.run_until_done()[rid].tokens)
        for p, got in zip(prompts, outs):
            want, _ = _serve(cfg, params, [p], 5, prefill_mode="scatter", **kw)
            assert got == want[0], (p, got, want[0])

    def test_chunk_boundary_sweep(self, setup):
        """Prompt lengths +/- 1 around page and chunk multiples (page_size 4,
        chunk 8): partial first chunks, exact-fit chunks, 1-token remainders."""
        cfg, params = setup
        kw = dict(slots=1, capacity=32, paged=True, page_size=4, n_pages=8)
        for n in (3, 4, 5, 7, 8, 9, 11, 12, 13, 15, 16, 17):
            prompt = [(7 * i + n) % 97 + 1 for i in range(n)]
            want, _ = _serve(cfg, params, [prompt], 4, prefill_mode="scatter", **kw)
            got, eng = _serve(cfg, params, [prompt], 4, prefill_mode="chunked",
                              prefill_chunk=8, **kw)
            assert got == want, (n, got, want)
            assert eng.pool.free_count == eng.n_pages, n

    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_n_samples_fork_with_midprefill_base(self, setup, kv_bits):
        """submit_n while the base is still mid-prefill: the forks wait at
        the queue head (never degrade), share ALL the base's pages once it
        reaches its admission state, and match independent serving."""
        cfg, params = setup
        req = Request(prompt=PRE + [31, 32], max_new_tokens=6)
        oracle = ContinuousEngine(cfg, params, slots=3, capacity=32, paged=True,
                                  page_size=4, n_pages=24, kv_cache_bits=kv_bits,
                                  prefill_chunk=4)
        rids_o = oracle.submit_n(req, 3)
        done_o = oracle.run_until_done()
        eng = ContinuousEngine(cfg, params, slots=3, capacity=32, paged=True,
                               page_size=4, n_pages=24, prefix_sharing=True,
                               kv_cache_bits=kv_bits, prefill_chunk=4)
        rids = eng.submit_n(req, 3)
        # base got one 4-token chunk at admission (prompt is 10 tokens) and
        # is still prefilling; both forks must be queued, not degraded
        assert eng.slots[0].prefilling and sum(s.active for s in eng.slots) == 1
        assert len(eng.queue) == 2
        while eng.slots[0].prefilling:
            eng.step()  # base finishes -> forks admitted as page-aligned forks
        assert eng.prefix_hits == 2  # both rode _admit_fork, neither degraded
        done = eng.run_until_done()
        assert eng.cow_copies >= 2  # boundary page forked away per diverger
        assert [done[r].tokens for r in rids] == [done_o[r].tokens for r in rids_o]
        assert eng.pool.free_count == eng.n_pages
        eng.pool.check()


# ---------------------------------------------------------------------------
# Admission state machine: interleaving, bounded stalls, no temp buffer
# ---------------------------------------------------------------------------


class TestAdmissionStateMachine:
    def test_no_temp_contiguous_buffer(self, setup):
        """Acceptance: the chunked admission path never touches the scatter
        prefill (whose temp [1, capacity] cache was the double buffer)."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, slots=2, capacity=32, paged=True,
                               page_size=4, n_pages=16, prefill_chunk=4)

        def boom(*a, **k):  # pragma: no cover - must never run
            raise AssertionError("scatter prefill called on the chunked path")

        eng._prefill = boom
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9], [9, 8, 7]]
        ids = [eng.submit(Request(prompt=p, max_new_tokens=5)) for p in prompts]
        done = eng.run_until_done()
        assert all(len(done[i].tokens) == 5 for i in ids)

    def test_long_admission_never_stalls_decodes(self, setup):
        """A long-prompt admission interleaves with running decodes: every
        tick decodes all non-prefilling active slots, and per-tick prefill
        compute never exceeds the chunk budget."""
        cfg, params = setup
        chunk = 4
        eng = ContinuousEngine(cfg, params, slots=3, capacity=64, paged=True,
                               page_size=4, n_pages=48, prefill_chunk=chunk)
        a = eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=30))
        b = eng.submit(Request(prompt=[4, 5, 6], max_new_tokens=30))
        eng.step()
        long_id = eng.submit(Request(prompt=[(i % 50) + 1 for i in range(40)],
                                     max_new_tokens=4))
        li = next(i for i, s in enumerate(eng.slots) if s.request_id == long_id)
        assert eng.slots[li].prefilling  # one chunk at admission, 36 to go
        stall_free_ticks = 0
        while eng.slots[li].active and eng.slots[li].prefilling:
            before = [len(eng.slots[i].generated) for i in range(3)]
            eng.step()
            m = eng.last_metrics
            assert m["prefill_tokens"] <= chunk
            for i in range(3):
                if i != li and eng.slots[i].active:
                    assert len(eng.slots[i].generated) == before[i] + 1, \
                        "a running decode stalled behind the admission"
                    stall_free_ticks += 1
        assert stall_free_ticks >= 8  # 36 tokens / 4-token chunks = 9 ticks
        done = eng.run_until_done()
        assert len(done) == 3
        # token-exact vs the same traffic served by the scatter engine
        oracle = ContinuousEngine(cfg, params, slots=3, capacity=64, paged=True,
                                  page_size=4, n_pages=48, prefill_mode="scatter")
        oa = oracle.submit(Request(prompt=[1, 2, 3], max_new_tokens=30))
        ob = oracle.submit(Request(prompt=[4, 5, 6], max_new_tokens=30))
        oracle.step()
        oc = oracle.submit(Request(prompt=[(i % 50) + 1 for i in range(40)],
                                   max_new_tokens=4))
        done_o = oracle.run_until_done()
        assert done[a].tokens == done_o[oa].tokens
        assert done[b].tokens == done_o[ob].tokens
        assert done[long_id].tokens == done_o[oc].tokens

    def test_midprefill_preemption_resumes_exactly(self, setup):
        """Preempting a slot that is still prefilling frees its pages and
        re-queues (prompt, generated-so-far); the re-admission restarts the
        chunked prefill and finishes token-exact."""
        cfg, params = setup
        p = [(3 * i) % 23 + 1 for i in range(14)]
        want, _ = _serve(cfg, params, [p], 6, slots=1, capacity=32, paged=True,
                         page_size=4, n_pages=8, prefill_mode="scatter")
        eng = ContinuousEngine(cfg, params, slots=1, capacity=32, paged=True,
                               page_size=4, n_pages=8, prefill_chunk=4)
        rid = eng.submit(Request(prompt=p, max_new_tokens=6))
        assert eng.slots[0].prefilling
        eng._preempt(0)  # yank it mid-prefill
        assert eng.pool.free_count == eng.n_pages
        done = eng.run_until_done()
        assert eng.preemptions == 1
        assert done[rid].tokens == want[0], (done[rid].tokens, want[0])

    def test_shared_prefix_skips_prefill_flops(self, setup):
        """Acceptance: on a fully-paged arch, a prefix-sharing admission
        starts its chunks AFTER the shared pages — measured prefill compute
        drops by exactly the shared token count, outputs unchanged."""
        cfg, params = setup
        assert arch_fully_paged(cfg)
        prompts = [PRE + [11, 12], PRE + [13, 14], PRE + [15, 16]]
        kw = dict(slots=3, capacity=32, paged=True, page_size=4, n_pages=24,
                  prefill_chunk=4)

        def serve_staggered(**extra):
            eng = ContinuousEngine(cfg, params, **kw, **extra)
            ids = [eng.submit(Request(prompt=prompts[0], max_new_tokens=4))]
            while eng.slots[0].prefilling:
                eng.step()  # finish writing the preamble before the others arrive
            ids += [eng.submit(Request(prompt=p, max_new_tokens=4)) for p in prompts[1:]]
            done = eng.run_until_done()
            return [done[i].tokens for i in ids], eng

        want, base = serve_staggered()
        got, eng = serve_staggered(prefix_sharing=True)
        assert got == want, (got, want)
        assert eng.prefix_hits == 2
        # admissions 2 and 3 each skipped the 8-token (2-page) preamble
        assert eng.prefill_tokens_skipped == 2 * len(PRE)
        assert eng.prefill_tokens_total == base.prefill_tokens_total - 2 * len(PRE)

    def test_concurrent_admissions_share_progressively(self, setup):
        """A second admission arriving while the first is mid-prefill shares
        the pages the first has ALREADY written (progressive index
        registration), not nothing."""
        cfg, params = setup
        long_pre = [(5 * i) % 17 + 1 for i in range(12)]
        eng = ContinuousEngine(cfg, params, slots=2, capacity=32, paged=True,
                               page_size=4, n_pages=24, prefix_sharing=True,
                               prefill_chunk=4)
        eng.submit(Request(prompt=long_pre + [99], max_new_tokens=3))
        assert eng.slots[0].prefilling  # 4 of 13 tokens written
        eng.submit(Request(prompt=long_pre + [98], max_new_tokens=3))
        assert eng.prefix_hits == 1  # shared the one already-written page
        assert eng.prefill_tokens_skipped == 4
        done = eng.run_until_done()
        want, _ = _serve(cfg, params, [long_pre + [99], long_pre + [98]], 3,
                         slots=2, capacity=32, paged=True, page_size=4,
                         n_pages=24, prefill_mode="scatter")
        assert [done[i].tokens for i in sorted(done)] == want
        assert eng.pool.free_count == eng.n_pages

    def test_interleaving_fuzz(self, setup):
        """Randomized mixed traffic (short and long prompts, interleaved
        submits and ticks): per-tick prefill compute never exceeds the chunk
        budget, every decode-eligible slot advances every tick, and all
        outputs come back token-exact vs a scatter-mode engine fed the
        identical submissions."""
        cfg, params = setup
        chunk = 4
        for seed in (0, 1, 2):
            rng = np.random.default_rng(seed)
            eng = ContinuousEngine(cfg, params, slots=3, capacity=48, paged=True,
                                   page_size=4, n_pages=64, prefill_chunk=chunk)
            oracle = ContinuousEngine(cfg, params, slots=3, capacity=48, paged=True,
                                      page_size=4, n_pages=64,
                                      prefill_mode="scatter")
            submitted = 0
            for _ in range(80):
                op = rng.choice(["submit", "step", "step", "step"])
                if op == "submit" and submitted < 8:
                    n = int(rng.choice([2, 3, 20, 28]))  # short or long prompt
                    prompt = [int(t) for t in rng.integers(1, 97, size=n)]
                    req = Request(prompt=prompt, max_new_tokens=int(rng.integers(2, 5)))
                    assert eng.submit(req) == oracle.submit(req)
                    submitted += 1
                else:
                    eligible = sum(s.active and not s.prefilling for s in eng.slots)
                    ticked = eng.step()
                    oracle.step()
                    if ticked:
                        m = eng.last_metrics
                        assert m["prefill_tokens"] <= chunk, m
                        assert m["tokens_this_tick"] >= eligible, \
                            "a decode-eligible slot stalled behind an admission"
            done = eng.run_until_done()
            done_o = oracle.run_until_done()
            assert set(done) == set(done_o) and len(done) == submitted
            for rid in done_o:
                assert done[rid].tokens == done_o[rid].tokens, (seed, rid)
            assert eng.pool.free_count == eng.n_pages

    def test_metrics_surface_prefill_counters(self, setup):
        cfg, params = setup
        _, eng = _serve(cfg, params, [[1, 2, 3, 4, 5, 6, 7, 8, 9]], 3, slots=2,
                        capacity=16, paged=True, page_size=4, prefill_chunk=4)
        m = eng.last_metrics
        for key in ("prefill_tokens", "tokens_this_tick", "free_pages",
                    "preemptions"):
            assert key in m, key
        assert any(r["prefill_tokens"] > 0 for r in eng.metrics_log)
        assert eng.prefill_tokens_total == 9


# ---------------------------------------------------------------------------
# Engine level: batched multi-slot prefill (the fused tick) vs per-slot chunked
# ---------------------------------------------------------------------------


class TestBatchedPrefillTick:
    """``prefill_mode="batched"``: one fixed-shape jitted call advances EVERY
    mid-prefill slot's next chunk per tick, so a steady tick issues at most
    {one batched prefill, one batched decode}.  Padding rows must be inert by
    construction (trash-page routing / ring scatter drops / dt=0 / a=1,b=0),
    so outputs are token-identical to the per-slot chunked engine."""

    @pytest.mark.parametrize("prefix", [False, True])
    @pytest.mark.parametrize(
        "arch", ["glm4-9b", "gemma3-27b", "recurrentgemma-2b"])
    def test_batched_matches_chunked_greedy(self, arch, prefix):
        """Acceptance: token-identical greedy outputs across fully-paged
        (glm4), window-ring mix (gemma3), and LRU/SSM resume
        (recurrentgemma), with prefix sharing on and off, under enough
        concurrent admissions that several slots are mid-prefill at once."""
        cfg = make_reduced(all_configs()[arch])
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        # 5 prompts onto 4 slots: the queued 5th repeats PRE, so it admits
        # AFTER the prefix pages are indexed -> exercises a prefix hit under
        # the batched tick (the first 4 admit before anything is indexed)
        prompts = [PRE + [int(t) for t in rng.randint(1, 97, size=n)]
                   for n in (13, 1)] + [[9, 8, 7], [1, 2]] + \
                  [PRE + [int(t) for t in rng.randint(1, 97, size=5)]]
        kw = dict(slots=4, capacity=32, paged=True, page_size=4,
                  prefill_chunk=8, prefix_sharing=prefix)
        want, _ = _serve(cfg, params, prompts, 6, prefill_mode="chunked", **kw)
        got, eng = _serve(cfg, params, prompts, 6, prefill_mode="batched", **kw)
        assert got == want, (got, want)
        assert eng.pool.free_count == eng.n_pages
        if prefix:
            assert eng.prefix_hits >= 1
        # several slots really were mid-prefill in one batched call
        assert any(m.get("batched_prefill_occupancy", 0) > 0.25
                   for m in eng.metrics_log)

    def test_one_prefill_dispatch_per_tick(self, setup):
        """>= 3 concurrent mid-prefill admissions advance in ONE batched
        jitted call: steady ticks issue at most 2 primary dispatches
        (batched prefill + decode), and the jitted-calls gauge proves it."""
        cfg, params = setup
        prompts = [[int(t) for t in np.arange(1, 22 + i)] for i in range(3)]
        eng = ContinuousEngine(cfg, params, slots=4, capacity=32, paged=True,
                               page_size=4, prefill_chunk=4,
                               prefill_mode="batched")
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=4))
        eng.step()  # admission tick: all 3 join the one batched call
        m = eng.last_metrics
        assert m["prefill_tokens"] == 12  # 3 rows x 4-token chunk, one call
        assert m["batched_prefill_occupancy"] == 0.75
        # registry: the batched entry replaces the first/cont chunk family
        # and is primary alongside decode
        fns = eng.jitted_functions()
        assert "prefill_chunk_batched" in fns
        assert "prefill_chunk_first" not in fns and "prefill_chunk_cont" not in fns
        primaries = [n for n, (_, _, p) in fns.items() if p]
        assert sorted(primaries) == ["decode", "prefill_chunk_batched"]
        eng.run_until_done()
        # steady ticks (no admissions/releases): <= 2 jitted calls each
        steady = [m for m in eng.metrics_log
                  if m.get("prefill_tokens", 0) and m.get("tokens_this_tick")]
        assert steady and all(m["jitted_calls"] <= 2 for m in steady)

    def test_batched_requires_paged(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="batched.*paged"):
            ContinuousEngine(cfg, params, slots=2, capacity=16,
                             prefill_mode="batched")

    def test_preemption_resumes_exactly(self, setup):
        """Mid-prefill preemption under the batched tick resumes token-exact
        (the reset flag rebuilds the victim's row state on re-admission)."""
        cfg, params = setup
        long = [int(t) for t in np.arange(1, 41)]
        short = [5, 4, 3]
        kw = dict(slots=2, capacity=48, paged=True, page_size=4, n_pages=14,
                  prefill_chunk=4)  # tight pool forces a preemption
        want, _ = _serve(cfg, params, [long, short], 4,
                         prefill_mode="chunked", **kw)
        got, eng = _serve(cfg, params, [long, short], 4,
                          prefill_mode="batched", **kw)
        assert got == want, (got, want)
        assert eng.pool.free_count == eng.n_pages
