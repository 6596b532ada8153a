"""Prefix-sharing / copy-on-write paged serving: physical page sharing
(refcount-asserted), CoW isolation, page-aligned parallel sampling, greedy
parity against the non-shared paged engine (the strict oracle — the decode
read path is untouched by sharing, tables just point at shared pages), int8
and window-ring composition, and a randomized ~200-step scheduler fuzz."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import all_configs, make_reduced
from repro.models.model import _layer_entries, init_params
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Request
from repro.serving.prefix_index import PrefixIndex

from tests._hyp import given, settings, st

PRE = [7, 7, 3, 5, 1, 2, 9, 4]  # 2 full pages at page_size=4 — shared preamble


@pytest.fixture(scope="module")
def setup():
    cfg = make_reduced(all_configs()["glm4-9b"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _serve(cfg, params, prompts, n_new, **kw):
    eng = ContinuousEngine(cfg, params, **kw)
    ids = [eng.submit(Request(prompt=p, max_new_tokens=n_new)) for p in prompts]
    done = eng.run_until_done()
    return [done[i].tokens for i in ids], eng


def _first_paged_self(cfg, caches):
    for sk, pk, ls, paged in _layer_entries(cfg):
        if paged:
            return caches[sk][pk]["self"]
    raise AssertionError("no paged layer")


# ---------------------------------------------------------------------------
# Prefix index unit behavior
# ---------------------------------------------------------------------------


class TestPrefixIndex:
    def test_insert_lookup_full_pages_only(self):
        idx = PrefixIndex(4)
        toks = list(range(10))  # 2 full pages + partial tail
        assert idx.insert(toks, [5, 9]) == 2
        assert idx.lookup(toks) == [5, 9]
        assert idx.lookup(toks[:7]) == [5]  # only 1 full page matches
        assert idx.lookup(toks, max_tokens=8) == [5, 9]
        assert idx.lookup(toks, max_tokens=7) == [5]  # admission's len-1 cap
        assert idx.lookup([1] + toks[1:]) == []  # different first chunk

    def test_first_writer_wins_and_eviction_holes(self):
        idx = PrefixIndex(4)
        toks = list(range(8))
        idx.insert(toks, [1, 2])
        assert idx.insert(toks, [3, 4]) == 0  # duplicates keep existing pages
        assert idx.lookup(toks) == [1, 2]
        idx.evict_pages([1])  # mid-chain hole: deeper match must not leak
        assert idx.lookup(toks) == []
        assert len(idx) == 1  # page 2's mapping survives, unreachable
        idx.insert(toks, [9, 7])  # refill the hole; chunk 1 keeps page 2
        assert idx.lookup(toks) == [9, 2]
        idx.evict_pages([9, 2])
        assert len(idx) == 0

    def test_duplicate_page_at_new_path_raises(self):
        idx = PrefixIndex(2)
        idx.insert([1, 2], [0])
        with pytest.raises(ValueError, match="already indexed"):
            idx.insert([3, 4], [0])


# ---------------------------------------------------------------------------
# Physical sharing + CoW isolation (refcount-asserted)
# ---------------------------------------------------------------------------


class TestPhysicalSharing:
    def test_two_slots_share_common_prefix_pages(self, setup):
        """Acceptance: two admitted requests with a >=2-page common prefix
        physically share those pages — same ids in both tables, refcount 2,
        occupancy counting them once."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, slots=2, capacity=32, paged=True,
                               page_size=4, n_pages=16, prefix_sharing=True)
        eng.submit(Request(prompt=PRE + [11], max_new_tokens=4))
        eng.submit(Request(prompt=PRE + [12, 13], max_new_tokens=4))
        assert all(s.active for s in eng.slots)
        shared = [int(p) for p in eng.tables.row(0)[:2]]
        assert [int(p) for p in eng.tables.row(1)[:2]] == shared
        assert all(eng.pool.refcount(p) == 2 for p in shared)
        # 2 shared + 1 private tail page each — 4 physical pages, not 6
        assert eng.pool.used_count == 4
        assert eng.prefix_hits == 1 and eng.prefix_hit_tokens == len(PRE)
        done = eng.run_until_done()
        assert len(done) == 2
        assert eng.pool.free_count == eng.n_pages and len(eng.prefix) == 0
        eng.pool.check()

    def test_cow_never_mutates_page_visible_to_another_slot(self, setup):
        """Fork two samples off one prompt whose boundary page is partial;
        the first divergent append must copy, and the shared prompt entries
        of the original page must be bit-identical afterwards."""
        cfg, params = setup
        eng = ContinuousEngine(cfg, params, slots=2, capacity=32, paged=True,
                               page_size=4, n_pages=16, prefix_sharing=True)
        prompt = PRE + [11, 12]  # 10 tokens: boundary page holds pos 8..9
        eng.submit_n(Request(prompt=prompt, max_new_tokens=5), 2)
        boundary = int(eng.tables.row(0)[2])
        assert int(eng.tables.row(1)[2]) == boundary
        assert eng.pool.refcount(boundary) == 2
        pool0 = _first_paged_self(cfg, eng.caches)
        before_k = np.asarray(pool0["k"][:, boundary, :2])  # prompt entries (all heads)
        before_pos = np.asarray(pool0["pos"][:, boundary, :2])
        eng.step()
        assert eng.cow_copies >= 1
        # tables diverged at the boundary entry; both slots still share 8..9
        assert int(eng.tables.row(0)[2]) != int(eng.tables.row(1)[2])
        pool1 = _first_paged_self(cfg, eng.caches)
        for b in (int(eng.tables.row(0)[2]), int(eng.tables.row(1)[2])):
            np.testing.assert_array_equal(np.asarray(pool1["pos"][:, b, :2]), before_pos)
            np.testing.assert_array_equal(np.asarray(pool1["k"][:, b, :2]), before_k)
        done = eng.run_until_done()
        assert len(done) == 2
        assert eng.pool.free_count == eng.n_pages
        eng.pool.check()

    def test_preempted_sharer_decrefs_not_frees(self, setup):
        """Engine-level regression for the owner-tag release bug: preempting
        a slot that shares prefix pages must leave them live for the other
        slot, and both requests must still finish token-exact."""
        cfg, params = setup
        prompts = [PRE + [21], PRE + [22]]
        want, _ = _serve(cfg, params, prompts, 6, slots=2, capacity=32,
                         paged=True, page_size=4, n_pages=16)
        eng = ContinuousEngine(cfg, params, slots=2, capacity=32, paged=True,
                               page_size=4, n_pages=16, prefix_sharing=True)
        ids = [eng.submit(Request(prompt=p, max_new_tokens=6)) for p in prompts]
        shared = [int(p) for p in eng.tables.row(0)[:2]]
        assert all(eng.pool.refcount(p) == 2 for p in shared)
        eng._preempt(1)  # the sharer departs mid-flight
        assert all(eng.pool.refcount(p) == 1 for p in shared), \
            "release must decref shared pages, not free them"
        assert len(eng.prefix) > 0  # still-live pages stay indexed
        done = eng.run_until_done()
        assert [done[i].tokens for i in ids] == want
        assert eng.preemptions == 1
        assert eng.pool.free_count == eng.n_pages and len(eng.prefix) == 0
        eng.pool.check()


# ---------------------------------------------------------------------------
# Greedy parity vs the non-shared paged engine (fp, int8, window rings)
# ---------------------------------------------------------------------------


class TestPrefixParity:
    @pytest.mark.parametrize("prefill_mode", ["chunked", "scatter"])
    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_matches_unshared_engine_greedy(self, setup, kv_bits, prefill_mode):
        """Acceptance: token-identical greedy outputs with sharing enabled,
        fp and int8 KV pages, while admissions actually hit the index —
        under both admission paths (the scatter oracle's scatter_start
        trash-routing is exactly what sharing exercises there)."""
        cfg, params = setup
        prompts = [PRE + [11], PRE + [12, 13], PRE + [14, 15, 16], [9, 8, 7]]
        want, _ = _serve(cfg, params, prompts, 5, slots=3, capacity=32,
                         kv_cache_bits=kv_bits, paged=True, page_size=4,
                         n_pages=24, prefill_mode=prefill_mode)
        got, eng = _serve(cfg, params, prompts, 5, slots=3, capacity=32,
                          kv_cache_bits=kv_bits, paged=True, page_size=4,
                          n_pages=24, prefix_sharing=True,
                          prefill_mode=prefill_mode)
        assert got == want, (got, want)
        assert eng.prefix_hits >= 2
        assert eng.pool.free_count == eng.n_pages and len(eng.prefix) == 0

    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_n_samples_fork_matches_independent_serving(self, setup, kv_bits):
        """Page-aligned parallel sampling: n greedy samples share all prompt
        pages (boundary included), diverge via CoW, and match n independent
        submissions of the same prompt bit-for-bit."""
        cfg, params = setup
        req = Request(prompt=PRE + [31, 32], max_new_tokens=6)
        oracle = ContinuousEngine(cfg, params, slots=3, capacity=32, paged=True,
                                  page_size=4, n_pages=24,
                                  kv_cache_bits=kv_bits)
        rids_o = oracle.submit_n(req, 3)  # no sharing: independent admissions
        done_o = oracle.run_until_done()
        eng = ContinuousEngine(cfg, params, slots=3, capacity=32, paged=True,
                               page_size=4, n_pages=24, prefix_sharing=True,
                               kv_cache_bits=kv_bits)
        rids = eng.submit_n(req, 3)
        # all three tables alias the same pages before divergence
        rows = [list(map(int, eng.tables.row(i)[:3])) for i in range(3)]
        assert rows[0] == rows[1] == rows[2]
        assert all(eng.pool.refcount(p) == 3 for p in rows[0])
        done = eng.run_until_done()
        assert [done[r].tokens for r in rids] == [done_o[r].tokens for r in rids_o]
        assert eng.cow_copies >= 2  # two of three holders had to fork away
        assert eng.pool.free_count == eng.n_pages
        eng.pool.check()

    def test_window_ring_mix_gemma3(self):
        """Sliding-window layers keep per-slot rings while global layers
        share pages — sharing parity and fork-copied rings on a local+global
        arch (gemma3)."""
        cfg = make_reduced(all_configs()["gemma3-27b"])
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompts = [PRE + [11, 12], PRE + [13], [1, 2, 3]]
        want, _ = _serve(cfg, params, prompts, 6, slots=2, capacity=24,
                         paged=True, page_size=4, n_pages=12)
        got, eng = _serve(cfg, params, prompts, 6, slots=2, capacity=24,
                          paged=True, page_size=4, n_pages=12,
                          prefix_sharing=True)
        assert got == want, (got, want)
        assert eng.prefix_hits >= 1
        # forks must row-copy the window rings (paged_copy_slot_leaves)
        req = Request(prompt=PRE + [41, 42], max_new_tokens=5)
        oracle = ContinuousEngine(cfg, params, slots=2, capacity=24, paged=True,
                                  page_size=4, n_pages=12)
        rids_o = oracle.submit_n(req, 2)
        done_o = oracle.run_until_done()
        eng2 = ContinuousEngine(cfg, params, slots=2, capacity=24, paged=True,
                                page_size=4, n_pages=12, prefix_sharing=True)
        rids = eng2.submit_n(req, 2)
        done = eng2.run_until_done()
        assert [done[r].tokens for r in rids] == [done_o[r].tokens for r in rids_o]
        assert eng2.pool.free_count == eng2.n_pages

    def test_metrics_surface_sharing_counters(self, setup):
        cfg, params = setup
        _, eng = _serve(cfg, params, [PRE + [1], PRE + [2]], 3, slots=2,
                        capacity=16, paged=True, page_size=4,
                        prefix_sharing=True)
        m = eng.last_metrics
        for key in ("shared_pages", "cow_copies", "prefix_hits",
                    "prefix_hit_tokens", "free_pages", "preemptions"):
            assert key in m, key
        assert any(r["shared_pages"] > 0 for r in eng.metrics_log)


# ---------------------------------------------------------------------------
# Randomized scheduler stress: ~200-step fuzz vs the non-prefix oracle
# ---------------------------------------------------------------------------


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_scheduler_fuzz_token_exact_and_drained(seed):
    """~200 random scheduler events — admits with overlapping prefixes,
    n-sample forks, decode ticks, forced preemptions, natural completions —
    through a tight sharing pool, against a generously-provisioned
    non-prefix paged engine fed the identical submissions.  Greedy decoding
    makes outputs timing-independent, so every request must come back
    token-exact, and the sharing engine must end fully drained (all pages
    free, empty index, internal invariants intact)."""
    cfg = make_reduced(all_configs()["glm4-9b"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)

    pre_a = [7, 7, 3, 5, 1, 2, 9, 4]  # 2 full pages
    pre_b = [6, 6, 6, 6]  # 1 full page
    eng = ContinuousEngine(cfg, params, slots=3, capacity=24, paged=True,
                           page_size=4, n_pages=14, prefix_sharing=True)
    oracle = ContinuousEngine(cfg, params, slots=3, capacity=24, paged=True,
                              page_size=4, n_pages=0)  # auto: never preempts

    submitted = 0
    for _ in range(200):
        op = rng.choice(["submit", "submit", "fork", "step", "step", "step",
                         "preempt"])
        if op == "submit" and submitted < 14:
            pre = [pre_a, pre_b, []][int(rng.integers(0, 3))]
            tail = [int(t) for t in rng.integers(100, 400, size=int(rng.integers(1, 4)))]
            req = Request(prompt=pre + tail,
                          max_new_tokens=int(rng.integers(2, 6)))
            a, b = eng.submit(req), oracle.submit(req)
            assert a == b  # identical submission order => aligned request ids
            submitted += 1
        elif op == "fork" and submitted < 14:
            tail = [int(t) for t in rng.integers(100, 400, size=2)]
            req = Request(prompt=pre_a + tail,
                          max_new_tokens=int(rng.integers(2, 6)))
            n = int(rng.integers(2, 4))
            assert eng.submit_n(req, n) == oracle.submit_n(req, n)
            submitted += n
        elif op == "step":
            eng.step()
            oracle.step()
        elif op == "preempt":
            active = [i for i, s in enumerate(eng.slots) if s.active]
            if active:
                eng._preempt(int(rng.choice(active)))
        eng.pool.check()

    done = eng.run_until_done()
    done_o = oracle.run_until_done()
    assert set(done) == set(done_o) and len(done) == submitted
    for rid in done_o:
        assert done[rid].tokens == done_o[rid].tokens, rid
    assert eng.prefix_hits > 0  # the traffic really exercised sharing
    assert eng.pool.free_count == eng.n_pages, "pool must drain"
    assert len(eng.prefix) == 0, "index must drain with the pool"
    assert oracle.pool.free_count == oracle.n_pages
    eng.pool.check()
