"""Continuous batching on top of the DS-MoE serving stack.

Production MoE serving (paper §5.5: "hundreds of GPUs to meet traffic")
cannot wait for a whole batch to finish before admitting new requests.  This
scheduler maintains a fixed pool of decode *slots*; each slot has its own
sequence position, requests are admitted into free slots with a per-slot
prefill, and every engine tick decodes all active slots in one batched
ragged decode step (per-row positions, masked sampling).

Two KV-storage models share the scheduler:

  * **contiguous** (default) — each slot owns a ``capacity``-token cache
    row.  Admission = a free slot.  Simple, but memory is reserved for the
    worst case: a 12-token request strands ``capacity - 12`` tokens.
  * **paged** (``paged=True``) — cache memory is a shared pool of
    ``page_size``-token pages (serving/kv_pool.py); each slot holds a
    static-shape block table.  Admission goes by *free-block count*, a
    sequence's table grows lazily as it decodes, pages return to the pool
    the moment a request finishes, and when the pool is exhausted the
    youngest slot is preempted back to the queue (its pages freed, its
    progress resumed later via re-prefill over prompt + generated tokens).
    Effective concurrent sequences per byte now scale with actual sequence
    lengths, not the worst case — and multiply with ``kv_cache_bits=8``.

Paged mode optionally adds **prefix sharing with copy-on-write**
(``prefix_sharing=True``): a radix index over full-page token chunks
(serving/prefix_index.py) maps live prompt prefixes to physical pages, so a
request whose context repeats an admitted prefix — shared system prompts,
few-shot preambles — points its block table at the existing pages
(refcounted via ``KVBlockPool.share``) and only the tail is written at
prefill.  Parallel sampling (``submit_n``) rides the same mechanism: n
samples of one prompt share ALL its pages, including the partially-filled
boundary page, and diverge lazily — before a slot appends into a page with
refcount > 1, the scheduler forks it a private copy (``pool.fork`` +
``paged_copy_page``), so a page visible to another slot is never mutated.
The decode read path is untouched by construction (tables just point at
shared pages), which is what makes greedy parity against the non-shared
paged engine a strict end-to-end oracle.

Paged admission is a **resumable multi-tick state machine** (chunked
prefill, the default): admission reserves the slot and every prompt page up
front (all-or-nothing, so free-block admission semantics are unchanged),
then the prompt's *compute* is spread over engine ticks — each ``step()``
runs at most ``prefill_chunk`` tokens of prefill (page-aligned chunks,
written straight into pool pages by ``models.model.paged_prefill_chunk``)
before decoding the already-running slots, so a long-prompt admission can
never stall running decodes for more than one chunk of compute.  A
prefix-sharing admission starts its first chunk AFTER the shared pages and
reads them in place through the block table, so sharing saves the prefill
FLOPs as well as the pages.  There is no temp contiguous prefill cache
anywhere in this path; ``prefill_mode="scatter"`` retains the PR 3/4
temp-contiguous-then-scatter admission as a parity oracle
(tests/test_chunked.py asserts token-identical greedy outputs).

Static shapes throughout: slot pool, page pool, and block tables are all
fixed, so the jitted decode step never recompiles as traffic arrives/leaves
— the property that makes continuous batching viable under XLA.  Chunked
prefill compiles once per distinct chunk length (the page-aligned budget
plus each prompt's final remainder), same order as the per-prompt-length
compiles of the scatter path.

``prefill_mode="batched"`` fuses the tick further: ALL mid-prefill slots
advance one chunk in a SINGLE jitted call
(``models.model.paged_prefill_chunk_batched``) on a fixed ``[slots,
prefill_chunk]`` stacked shape — per-slot active masks, -1-padded position
rows, trash-routed tables for inactive rows — so an engine tick issues at
most two primary dispatches ({one batched prefill, one batched decode})
regardless of how many admissions are mid-prefill, and the batched entry
compiles exactly once (no per-chunk-length compiles at all).  Per-row
numerics are identical to the per-slot chunked path (tests/test_chunked.py
asserts token-exact greedy parity); "chunked" stays the default because the
batched call pads every row to the full chunk budget — it wins when several
admissions overlap (dispatch count), "chunked" when prefill traffic is
sparse (no padded compute).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, PagedKVConfig
from repro.core.gating import summarize_routing
from repro.models.model import (
    arch_fully_paged,
    init_caches,
    init_paged_caches,
    paged_copy_page,
    paged_copy_slot_leaves,
    paged_prefill_chunk,
    paged_prefill_chunk_batched,
    paged_prefill_into_slot,
    paged_ragged_decode_step,
    paged_reset_page_tails,
    paged_reset_pages,
    paged_verify_chunk_batched,
    prefill_into_slot,
    ragged_decode_step,
)
from repro.obs import Obs
from repro.serving.engine import Request, Response
from repro.serving.kv_pool import BlockTables, KVBlockPool
from repro.serving.prefix_index import PrefixIndex
from repro.serving.sampling import sample
from repro.serving.spec import Drafter, accept_length


@dataclass
class SlotState:
    """One decode slot's scheduler-side state.

    Invariants the scheduler maintains (fuzzed by tests/test_prefix.py and
    tests/test_chunked.py):

      * ``prefilling`` implies ``active`` — the slot holds its pages (all
        reserved at admission) but is excluded from decode ticks and from
        lazy growth / CoW in ``_ensure_pages``;
      * while ``prefilling``, ``pos == prefill_done`` = positions already
        written to pages (a prefix-sharing admission starts both at
        ``len(shared) * page_size``), and ``generated`` holds only tokens
        carried over from a preemption;
      * once prefill completes, ``generated[-1]`` is sampled but not yet
        written — the state a parallel-sample fork can share wholesale.
    """

    request_id: int = -1
    pos: int = 0  # next absolute position
    generated: List[int] = field(default_factory=list)
    budget: int = 0
    active: bool = False
    admit_seq: int = -1  # admission order — youngest-first preemption key
    prompt_len: int = 0  # original (untruncated) prompt length
    # The request's base prompt, EXCLUDING generated tokens.  Preemption
    # re-queues (prompt, generated) separately; re-admission rebuilds the
    # context as (prompt + generated)[-keep:].  Storing the admitted context
    # here instead would duplicate the generated prefix on a second
    # preemption of the same request.
    prompt: List[int] = field(default_factory=list)
    # Last-context-token logits from this slot's admission prefill ([1, V]
    # numpy), kept under prefix sharing so parallel-sample forks admitted
    # before the base's first decode tick can draw their first token without
    # recomputing the prefill.
    prefill_logits: Optional[np.ndarray] = None
    # Chunked-prefill progress (paged admission state machine): the admitted
    # context still being written, how many positions are done, and whether
    # the first chunk ran yet (it must RESET the per-slot leaves — the row
    # still holds the slot's previous occupant's ring/SSM state).
    prefilling: bool = False
    prefill_ctx: List[int] = field(default_factory=list)
    prefill_done: int = 0
    prefill_started: bool = False
    # Tokens by cache position: ``seq[j]`` is the token whose K/V lives (or,
    # for ``j == pos``, will live) at position ``j`` — context followed by
    # generated tokens.  Maintained from prefill completion on, with
    # ``len(seq) == pos + 1`` and ``seq[pos] == generated[-1]`` (the sampled
    # but not-yet-written current token).  Speculative decoding force-feeds
    # the drafter from it and rebuilds the drafter's cache on resync.
    seq: List[int] = field(default_factory=list)


@dataclass
class _Pending:
    """Queue entry.  ``generated`` is non-empty for preempted requests: on
    re-admission the engine prefills over ``prompt + generated`` so greedy
    decoding resumes exactly where it left off.  ``fork_of`` >= 0 marks a
    parallel sample of the request with that id (submit_n): if its base is
    still at its admission state when this entry reaches the queue head, the
    fork shares ALL the base's pages instead of prefilling; otherwise it
    degrades to an ordinary request (prefix-index sharing still applies)."""

    rid: int
    prompt: List[int]
    budget: int  # total response budget (already clamped to capacity - 1)
    generated: List[int]
    prompt_len: int
    fork_of: int = -1


class ContinuousEngine:
    """Slot-pool continuous batching.  ``step()`` = one engine tick (at most
    one chunk budget of admission prefill, then one decode step); requests
    are admitted on submit() whenever a slot (and, in paged mode, enough free
    pages for the WHOLE prompt — all-or-nothing) is available.

    Scheduler invariants, and the tests that hold them to account:

      * a refcount>1 page is never written — CoW before every divergent
        append, trash-routed prefill writes over shared entries
        (tests/test_prefix.py CoW-isolation, tests/test_kv_pool_prop.py);
      * mid-prefill slots never decode, and the decode step never touches
        their pages (table rows masked to -1) — tests/test_chunked.py;
      * per tick, admission prefill costs at most ``prefill_chunk`` tokens
        and every decode-eligible slot advances (bounded head-of-line
        blocking — tests/test_chunked.py interleaving fuzz);
      * preemption (youngest first) is token-exact from ANY state, including
        mid-prefill, because re-admission replays (prompt + generated)
        through the same greedy path — tests/test_paged.py round-trips;
      * the pool and prefix index drain to empty with traffic
        (tests/test_prefix.py scheduler fuzz).

    Like ``Engine``, accepts MoQ-quantized params (``QuantizedArray`` leaves
    from ``repro.quant.quantize_params``) transparently."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4, capacity: int = 256,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
                 eos_id: int = -1, seed: int = 0, kv_cache_bits: int = 0,
                 paged: bool = False, page_size: Optional[int] = None,
                 n_pages: Optional[int] = None, prefix_sharing: bool = False,
                 prefill_chunk: int = 0, prefill_mode: str = "chunked",
                 paged_cfg: Optional[PagedKVConfig] = None,
                 obs: Optional[Obs] = None,
                 spec_draft: Optional[tuple] = None, spec_k: int = 4):
        if paged_cfg is not None:
            # bundled form of the same knobs (configs.base.PagedKVConfig);
            # mixing it with the loose kwargs would silently shadow them
            if (paged or page_size is not None or n_pages is not None
                    or prefix_sharing or prefill_chunk):
                raise ValueError(
                    "pass either paged_cfg or paged/page_size/n_pages/"
                    "prefix_sharing/prefill_chunk, not both"
                )
            paged = True
            page_size = paged_cfg.page_size
            n_pages = paged_cfg.n_pages
            prefix_sharing = paged_cfg.prefix_sharing
            prefill_chunk = paged_cfg.prefill_chunk
        if prefix_sharing and not paged:
            raise ValueError("prefix_sharing requires paged=True (block tables)")
        if prefill_mode not in ("chunked", "batched", "scatter"):
            raise ValueError(
                f"prefill_mode must be 'chunked', 'batched' or 'scatter', got {prefill_mode!r}"
            )
        if prefill_mode == "batched" and not paged:
            raise ValueError(
                "prefill_mode='batched' requires paged=True: the batched chunk "
                "prefill writes directly into pool pages through block tables"
            )
        if spec_draft is not None:
            # draft-then-verify speculative decoding (serving/spec.py):
            # spec_draft = (drafter ModelConfig, drafter params)
            if not paged:
                raise ValueError(
                    "speculative decoding requires paged=True: rollback is "
                    "implemented as dropping CoW page forks"
                )
            if temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: verification accepts "
                    "the longest argmax-agreeing draft prefix, which is exact "
                    "for temperature=0 and has no sampling analogue here"
                )
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if spec_draft[0].vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"drafter vocab_size {spec_draft[0].vocab_size} != target "
                    f"vocab_size {cfg.vocab_size}: drafted token ids must BE "
                    "target token ids for verification to compare them"
                )
        from repro.quant import prepare_params_for_serving
        from repro.serving.ep import MeshCall, init_engine_mesh, place_params

        # EP serving mesh (cfg.ep_mesh): resolve BEFORE cfg is captured by
        # the jit closures below — the mesh rewrites moe_impl to the
        # shard_map serving schedule (serving/ep.py, core/moe_serve.py).
        self._mesh, self._mesh_rules, cfg = init_engine_mesh(cfg)
        self.cfg = cfg
        if spec_draft is not None and self._mesh is not None:
            raise NotImplementedError(
                "speculative decoding is not implemented over an "
                "expert-parallel serving mesh: the verify window's CoW fork "
                "plan is host-side per slot while the mesh replicates the page "
                "pool per rank — run without cfg.ep_mesh / --ep-devices, or "
                "drop --spec-draft"
            )

        if self._mesh is not None:
            from repro.parallel.sharding import use_mesh

            with use_mesh(self._mesh, self._mesh_rules):
                placed = prepare_params_for_serving(cfg, params)
            self.params = place_params(self._mesh, self._mesh_rules, placed)
        else:
            self.params = prepare_params_for_serving(cfg, params)
        self.n_slots = slots
        self.capacity = capacity
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.kv_cache_bits = kv_cache_bits
        self.paged = paged
        self.prefix_sharing = prefix_sharing
        self.prefill_mode = prefill_mode
        self.prefix: Optional[PrefixIndex] = None
        if paged:
            self.page_size = page_size = int(page_size or 16)
            # tokens of prefill compute per admission tick (0 = auto); chunk
            # boundaries are page-aligned, so at least one page per tick
            self.prefill_chunk = int(prefill_chunk) if prefill_chunk else max(64, page_size)
            if self.prefill_chunk < page_size:
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} must be >= page_size="
                    f"{page_size} (chunk boundaries are page-aligned)"
                )
            # prefix sharing skips the shared prefix's prefill COMPUTE only
            # when every mixer's state is paged; window-ring / SSM / LRU
            # per-slot state must be rebuilt by running the prefix (its page
            # writes are trash-routed — shared pages stay read-only)
            self._skip_shared_compute = arch_fully_paged(cfg)
            self.max_pages = -(-capacity // page_size)  # table entries per slot
            # n_pages None/0 = auto: slots * pages-per-capacity, i.e. the
            # contiguous worst case (same convention as EngineConfig/--pages)
            self.n_pages = int(n_pages) if n_pages else slots * self.max_pages
            if self.n_pages < self.max_pages:
                raise ValueError(
                    f"n_pages={self.n_pages} cannot hold even one full-capacity "
                    f"sequence ({self.max_pages} pages of {page_size})"
                )
            self.pool = KVBlockPool(self.n_pages, page_size)
            self.tables = BlockTables(slots, self.max_pages)
            if prefix_sharing:
                self.prefix = PrefixIndex(page_size)
            # kv_cache_bits=8 composes: int8 pages (~4x fewer bytes per cache
            # token) x fragmentation-free packing of those tokens
            self.caches = init_paged_caches(
                cfg, slots, capacity, n_pages=self.n_pages, page_size=page_size,
                kv_bits=kv_cache_bits,
            )
        else:
            # kv_cache_bits=8: pooled slot caches live as int8 QuantizedKV —
            # ~4x more slot-capacity per byte of cache memory; admission
            # prefill and ragged decode quantize on write
            self.caches = init_caches(cfg, slots, capacity, kv_bits=kv_cache_bits)
        if self._mesh is not None:
            # slot (batch) dim data-parallel over the EP axes when divisible;
            # pool pages + block-table state replicated (each rank reads only
            # its slots' pages — the host scheduler stays mesh-agnostic)
            from repro.serving.ep import place_caches

            self.caches = place_caches(
                self._mesh, self._mesh_rules, self.caches, slots=slots,
                n_pages=self.n_pages if paged else None,
            )
        self.slots = [SlotState() for _ in range(slots)]
        self.queue: List[_Pending] = []
        self.done: Dict[int, Response] = {}
        self.preemptions = 0
        self.cow_copies = 0  # pages privately duplicated before a divergent append
        self.prefix_hits = 0  # admissions that shared at least one indexed page
        self.prefix_hit_tokens = 0  # context tokens served from shared pages
        self.prefill_tokens_total = 0  # prompt tokens actually computed at prefill
        # context tokens whose prefill compute was SKIPPED because their K/V
        # was read from shared pages in place (chunked mode only — the
        # scatter oracle recomputes them; == prefix_hit_tokens there)
        self.prefill_tokens_skipped = 0
        self.metrics_log: List[dict] = []
        # Shared prefill budget for the CURRENT tick (None outside step()):
        # admissions triggered mid-tick (a completion freeing a slot for the
        # queue) draw their synchronous first chunk from THIS budget, so one
        # tick never runs more than prefill_chunk tokens of prefill no
        # matter how many admissions it cascades into.
        self._tick_budget: Optional[int] = None
        # prefill tokens computed during the current tick (both chunked and
        # batched modes add to it; _end_tick_prefill drains it)
        self._tick_prefill_done = 0
        # jitted-function invocations since the last recorded tick (every
        # _jit_registry fn call site increments it) and, in batched mode, the
        # fraction of slot rows carrying a real chunk in this tick's batched
        # prefill call — the two "fused tick" gauges
        self._jit_calls_tick = 0
        self._batched_occ_tick = 0.0
        self._metrics_cap = 65_536  # keep a bounded telemetry window
        self.last_metrics: dict = {}
        self._tick = 0
        self._next_id = 0
        self._admit_counter = 0
        self._key = jax.random.PRNGKey(seed)
        self._cur_token = np.zeros((slots,), np.int32)

        # -- observability ------------------------------------------------
        # Default Obs(): metrics on (they ARE the per-tick telemetry source,
        # ~µs/tick), tracer off (no-op fast path), routing collection off
        # (it changes the decode step's jitted return signature, so it is an
        # explicit opt-in at construction).  Obs.disabled() = all-off
        # benchmark baseline for the <1%-overhead guard.
        self.obs = obs if obs is not None else Obs()
        # hoisted enabled-check: the hot path tests one attribute, not three
        self._tr = self.obs.tracer if self.obs.tracer.enabled else None
        # tick phases: profiler annotations always (free with no profiler
        # running), engine-track spans too when the tracer is on
        self._phase = self.obs.tracer.phase
        M = self.obs.metrics
        self._h_queue = M.histogram("serve.queue_wait_s")
        self._h_ttft = M.histogram("serve.ttft_s")
        self._h_tpot = M.histogram("serve.tpot_s", lo=1e-5, hi=10.0)
        self._h_tick = M.histogram("serve.tick_s")
        self._h_preempts = M.histogram("serve.preempts_per_req", unit="",
                                       lo=1.0, hi=1024.0, n_buckets=10)
        self._c_submitted = M.counter("serve.requests_submitted", unit="req")
        self._c_completed = M.counter("serve.requests_completed", unit="req")
        self._c_decode_toks = M.counter("serve.decode_tokens", unit="tok")
        self._c_prefill_toks = M.counter("serve.prefill_tokens_computed", unit="tok")
        self._c_prefill_skip = M.counter("serve.prefill_tokens_skipped", unit="tok")
        self._c_preempt = M.counter("serve.preemptions")
        self._c_cow = M.counter("serve.cow_copies", unit="page")
        self._c_prefix_hits = M.counter("serve.prefix_hits")
        self._c_prefix_toks = M.counter("serve.prefix_hit_tokens", unit="tok")
        self._c_retraces = M.counter("serve.retraces", unit="compile")
        self._g_active = M.gauge("serve.active_slots")
        self._g_queue = M.gauge("serve.queue_depth")
        self._g_free_pages = M.gauge("serve.free_pages")
        self._g_occupancy = M.gauge("serve.page_occupancy")
        self._g_peak_occ = M.gauge("serve.peak_page_occupancy")
        self._g_shared = M.gauge("serve.shared_pages")
        self._g_jit_calls = M.gauge("serve.jitted_calls_per_tick", unit="call")
        self._g_batch_occ = M.gauge("serve.batched_prefill_occupancy")
        self._g_r_drop = M.gauge("routing.dropped_frac")
        self._g_r_ent = M.gauge("routing.entropy", unit="nat")
        self._g_r_imb = M.gauge("routing.imbalance")
        # per-request SLO state: t_submit/t_admit/t_first/t_last/n_tokens/
        # preempts; popped into histograms at completion
        self._req_obs: Dict[int, dict] = {}
        routing = self.obs.routing

        # -- speculative decoding: drafter + verify plumbing ----------------
        self.spec_k = int(spec_k) if spec_draft is not None else 0
        self.drafter: Optional[Drafter] = None
        self._spec_commit = None
        self._spec_tick_m: dict = {}
        if spec_draft is not None:
            if routing:
                raise ValueError(
                    "routing collection is incompatible with speculative "
                    "decoding: the verify pass replaces the plain decode step "
                    "and does not return RoutingStats"
                )
            dcfg, dparams = spec_draft
            self.drafter = Drafter(
                dcfg, prepare_params_for_serving(dcfg, dparams),
                slots=slots, capacity=capacity, spec_k=self.spec_k,
            )
            self._h_accept = M.histogram(
                "spec.accept_rate", unit="", lo=1.0 / (4 * self.spec_k),
                hi=1.0 + 1e-9, n_buckets=16)
            self._h_tok_verify = M.histogram(
                "spec.tokens_per_verify", unit="tok", lo=1.0,
                hi=float(self.spec_k + 1) + 1e-9, n_buckets=16)
            self._c_spec_drafted = M.counter("spec.draft_tokens", unit="tok")
            self._c_spec_accepted = M.counter("spec.accepted_tokens", unit="tok")
            self._c_spec_verifies = M.counter("spec.verify_windows")
            self._c_spec_commit_pages = M.counter("spec.committed_pages", unit="page")
            self._c_spec_rollback_pages = M.counter("spec.rolled_back_pages", unit="page")
            self._c_spec_resyncs = M.counter("spec.draft_resyncs")

        def _telemetry(*tel):
            # the decode step's third output: the dispatch's counters
            # always (the routed-expert counts, int32 [n_moe_layers], and on
            # an EP mesh the rows per rank, int32 [n_moe_layers, ep]; read
            # back with the sampled tokens), the routing-stats tree when
            # collection is on
            out = {"counters": tel[-1]}
            if routing:
                out["routing"] = tel[0]
            return out

        if paged:
            def _step(params, tokens, positions, active, caches, tables):
                # normalized 3-tuple return so the call site rebinds the
                # donated caches in one unpacking assignment — the donation
                # auditor's required shape
                logits, caches, *tel = paged_ragged_decode_step(
                    cfg, params, tokens, positions, active, caches, tables,
                    return_routing=routing, return_routed=True,
                )
                return logits, caches, _telemetry(*tel)

            self._decode = jax.jit(_step, donate_argnums=(4,))

            def _prefill_one(params, tokens, positions, slot, caches, table_row, scatter_start):
                return paged_prefill_into_slot(
                    cfg, params, tokens, positions, slot, caches, table_row,
                    capacity=capacity, kv_bits=kv_cache_bits,
                    scatter_start=scatter_start,
                )

            self._prefill = jax.jit(_prefill_one, donate_argnums=(4,))

            if prefill_mode == "batched":
                # ONE fixed-shape entry covers every mid-prefill slot's chunk
                # per tick; reset/active are traced row masks, so the batched
                # call compiles exactly once — the per-slot first/cont jits
                # are deliberately NOT built in this mode (the jit registry,
                # watchdog, and predict_compiles key sets stay coherent)
                def _prefill_chunk_batched_fn(params, tokens, positions, reset,
                                              active, last_idx, caches, tables):
                    return paged_prefill_chunk_batched(
                        cfg, params, tokens, positions, reset, active, last_idx,
                        caches, tables, capacity=capacity,
                        kv_bits=kv_cache_bits, page_size=page_size,
                    )

                self._prefill_chunk_batched = jax.jit(
                    _prefill_chunk_batched_fn, donate_argnums=(6,))
            else:
                def _prefill_chunk_fn(params, tokens, positions, slot, caches, table_row, *, reset):
                    return paged_prefill_chunk(
                        cfg, params, tokens, positions, slot, caches, table_row,
                        capacity=capacity, kv_bits=kv_cache_bits, page_size=page_size,
                        reset=reset,
                    )

                # one compilation per distinct chunk length (budget + remainders)
                # x {first, continuation} — the first chunk of an admission resets
                # the slot's per-slot leaves (previous occupant's state), later
                # chunks resume them
                self._prefill_chunk_first = jax.jit(
                    functools.partial(_prefill_chunk_fn, reset=True), donate_argnums=(4,))
                self._prefill_chunk_cont = jax.jit(
                    functools.partial(_prefill_chunk_fn, reset=False), donate_argnums=(4,))
            self._reset_pages = jax.jit(
                lambda caches, mask: paged_reset_pages(cfg, caches, mask),
                donate_argnums=(0,),
            )
            # CoW device copy + parallel-sampling slot fork (src/dst traced)
            self._copy_page = jax.jit(
                lambda caches, src, dst: paged_copy_page(cfg, caches, src, dst),
                donate_argnums=(0,),
            )
            self._copy_slot = jax.jit(
                lambda caches, src, dst: paged_copy_slot_leaves(cfg, caches, src, dst),
                donate_argnums=(0,),
            )
            if self.drafter is not None:
                def _verify_fn(params, tokens, positions, active, caches, tables):
                    logits, caches = paged_verify_chunk_batched(
                        cfg, params, tokens, positions, active, caches, tables,
                        capacity=capacity, kv_bits=kv_cache_bits,
                        page_size=page_size,
                    )
                    # greedy-only engine: argmax inside the jit (identical to
                    # sample() at temperature 0) keeps the per-tick host sync
                    # to [slots, k + 1] int32 instead of full logits
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

                self._verify = jax.jit(_verify_fn, donate_argnums=(4,))
                self._spec_reset_tail = jax.jit(
                    lambda caches, pages, offs: paged_reset_page_tails(
                        cfg, caches, pages, offs),
                    donate_argnums=(0,),
                )
                if not arch_fully_paged(cfg):
                    # recurrent per-slot state (window rings, SSM/LRU, conv
                    # prefixes) cannot be rolled back, so verify leaves it
                    # untouched; this separate pass advances it over the
                    # ACCEPTED tokens only, after the page handoff.  Its pool
                    # writes are inert: every accepted position is already
                    # stored in a committed page, so the `already` write guard
                    # trash-routes the rewrite.
                    def _spec_commit_fn(params, tokens, positions, reset,
                                        active, last_idx, caches, tables):
                        return paged_prefill_chunk_batched(
                            cfg, params, tokens, positions, reset, active,
                            last_idx, caches, tables, capacity=capacity,
                            kv_bits=kv_cache_bits, page_size=page_size,
                        )

                    self._spec_commit = jax.jit(
                        _spec_commit_fn, donate_argnums=(6,))
        else:
            def _step(params, tokens, positions, active, caches):
                logits, caches, *tel = ragged_decode_step(
                    cfg, params, tokens, positions, active, caches,
                    return_routing=routing, return_routed=True,
                )
                return logits, caches, _telemetry(*tel)

            self._decode = jax.jit(_step, donate_argnums=(4,))

            def _prefill_one(params, tokens, positions, slot, caches):
                # single-request prefill written into the pooled caches at `slot`
                return prefill_into_slot(cfg, params, tokens, positions, slot, caches)

            self._prefill = jax.jit(_prefill_one, donate_argnums=(4,))

        # Jit registry: name -> (fn, donate_argnums, primary).  The SINGLE
        # source of truth for which jitted functions exist, what they donate,
        # and which carry the steady-state never-retrace contract — the
        # retrace watchdog registers from it below (primary = non-aux) and
        # the static analysis suite reads it back via jitted_functions() /
        # shape_contract(), so runtime and trace-time checks cannot drift.
        # Non-primary fns legitimately compile late (novel prompt/chunk
        # lengths, first page-reset/CoW): counted, no steady-state warning.
        self._jit_registry = {"decode": (self._decode, (4,), True),
                              "prefill": (self._prefill, (4,), False)}
        if paged:
            if prefill_mode == "batched":
                # fixed-shape, compiles once — it carries the steady-state
                # never-retrace contract alongside decode (primary): the
                # "fused tick" is at most these two dispatches
                self._jit_registry["prefill_chunk_batched"] = (
                    self._prefill_chunk_batched, (6,), True)
            else:
                self._jit_registry.update({
                    "prefill_chunk_first": (self._prefill_chunk_first, (4,), False),
                    "prefill_chunk_cont": (self._prefill_chunk_cont, (4,), False),
                })
            self._jit_registry.update({
                "reset_pages": (self._reset_pages, (0,), False),
                "copy_page": (self._copy_page, (0,), False),
                "copy_slot": (self._copy_slot, (0,), False),
            })
            if self.drafter is not None:
                # fixed [slots, k + 1] / [k + 1, slots] shapes: the whole
                # speculative tick is primary never-retrace machinery except
                # the drafter's lazy per-context-length prefill
                self._jit_registry.update({
                    "verify": (self._verify, (4,), True),
                    "spec_reset_tail": (self._spec_reset_tail, (0,), True),
                    "draft_propose": (self.drafter._propose, (5,), True),
                    "draft_prefill": (self.drafter._prefill, (4,), False),
                })
                if self._spec_commit is not None:
                    self._jit_registry["spec_commit"] = (
                        self._spec_commit, (6,), True)
        if self._mesh is not None:
            # every entry point (execution, lower, eval_shape) runs under the
            # serving mesh; attribute forwarding keeps the watchdog's
            # _cache_size probe and the analysis gate working unchanged
            for _name in list(self._jit_registry):
                _fn, _don, _primary = self._jit_registry[_name]
                _w = MeshCall(_fn, self._mesh, self._mesh_rules)
                self._jit_registry[_name] = (_w, _don, _primary)
                setattr(self, "_" + _name, _w)
        wd = self.obs.watchdog
        for _name, (_fn, _don, _primary) in self._jit_registry.items():
            wd.register(_name, _fn, aux=not _primary)

    # -- declared contracts for the static analysis suite ----------------
    def jitted_functions(self) -> dict:
        """name -> (jitted fn, donate_argnums, primary) for every function a
        tick can invoke — what the donation auditor and contract checker
        audit, and the same classification the retrace watchdog enforces."""
        return dict(self._jit_registry)

    def shape_contract(self) -> list:
        """Declared compile-shape contract: the CLOSED set of signatures each
        jitted function may be called with, derived from the same config
        values that size the real buffers (slots / capacity / page geometry /
        chunk budget).  ``analysis.contracts.check_contract`` abstract-traces
        these; ``check_closure`` verifies scheduler-reachable states stay
        inside them."""
        from repro.analysis.contracts import ContractEntry

        aval = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        params = jax.tree.map(aval, self.params)
        caches = jax.tree.map(aval, self.caches)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        boolv = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bool_)
        S = self.n_slots

        def entry(name, make, points, sample):
            fn, don, primary = self._jit_registry[name]
            return ContractEntry(name=name, fn=fn, make=make,
                                 points=tuple(points), sample=tuple(sample),
                                 primary=primary, donate_argnums=don)

        # admission context lengths: at least `remaining` of the capacity is
        # reserved for decode, so a prefilled context never exceeds cap - 1
        ctx_lens = range(1, self.capacity)
        ctx_sample = sorted({1, 2, min(16, self.capacity - 1), self.capacity - 1})
        out = []
        if self.paged:
            MP = self.max_pages
            out.append(entry(
                "decode",
                lambda: (params, i32(S, 1), i32(S), boolv(S), caches, i32(S, MP)),
                [()], [()]))
            out.append(entry(
                "prefill",
                lambda n: (params, i32(1, n), i32(1, n), i32(), caches, i32(MP), i32()),
                [(n,) for n in ctx_lens], [(n,) for n in ctx_sample]))
            if self.prefill_mode == "batched":
                # ONE fixed signature: [slots, prefill_chunk] stacked chunks
                # (ragged rows ride as -1-padded positions), so the batched
                # entry has a singleton contract — the static-shape property
                # that makes it a primary never-retrace function
                C = self.prefill_chunk
                out.append(entry(
                    "prefill_chunk_batched",
                    lambda: (params, i32(S, C), i32(S, C), boolv(S), boolv(S),
                             i32(S), caches, i32(S, MP)),
                    [()], [()]))
            else:
                # chunk lengths: non-final chunks are page-aligned budget
                # slices, the final chunk is the context remainder — any
                # length from 1 to the per-tick budget is admissible, nothing
                # longer
                chunk_lens = range(1, self.prefill_chunk + 1)
                chunk_sample = sorted({1, max(1, self.page_size - 1), self.page_size,
                                       min(self.page_size + 1, self.prefill_chunk),
                                       self.prefill_chunk})
                for nm in ("prefill_chunk_first", "prefill_chunk_cont"):
                    out.append(entry(
                        nm,
                        lambda n: (params, i32(1, n), i32(1, n), i32(), caches, i32(MP)),
                        [(n,) for n in chunk_lens], [(n,) for n in chunk_sample]))
            out.append(entry(
                "reset_pages",
                lambda: (caches, jax.ShapeDtypeStruct((self.n_pages + 1,), jnp.bool_)),
                [()], [()]))
            for nm in ("copy_page", "copy_slot"):
                out.append(entry(nm, lambda: (caches, i32(), i32()), [()], [()]))
            if self.drafter is not None:
                K1 = self.spec_k + 1
                dparams = jax.tree.map(aval, self.drafter.params)
                dcaches = jax.tree.map(aval, self.drafter.caches)
                out.append(entry(
                    "verify",
                    lambda: (params, i32(S, K1), i32(S, K1), boolv(S), caches,
                             i32(S, MP)),
                    [()], [()]))
                out.append(entry(
                    "spec_reset_tail",
                    lambda: (caches, i32(S), i32(S)),
                    [()], [()]))
                if "spec_commit" in self._jit_registry:
                    out.append(entry(
                        "spec_commit",
                        lambda: (params, i32(S, K1), i32(S, K1), boolv(S),
                                 boolv(S), i32(S), caches, i32(S, MP)),
                        [()], [()]))
                out.append(entry(
                    "draft_propose",
                    lambda: (dparams, i32(K1, S), boolv(K1, S), i32(K1, S),
                             boolv(K1, S), dcaches),
                    [()], [()]))
                # lazy drafter (re)prefill: one [1, n] signature per distinct
                # committed-sequence length, same family as admission prefill
                out.append(entry(
                    "draft_prefill",
                    lambda n: (dparams, i32(1, n), i32(1, n), i32(), dcaches),
                    [(n,) for n in ctx_lens], [(n,) for n in ctx_sample]))
        else:
            out.append(entry(
                "decode",
                lambda: (params, i32(S, 1), i32(S), boolv(S), caches),
                [()], [()]))
            out.append(entry(
                "prefill",
                lambda n: (params, i32(1, n), i32(1, n), i32(), caches),
                [(n,) for n in ctx_lens], [(n,) for n in ctx_sample]))
        return out

    # -- request-lifecycle observability hooks -------------------------
    # Span taxonomy (docs/OBSERVABILITY.md): track ("request", rid) carries
    # queued -> prefill -> decode spans (preempted / prefix_hit / complete
    # instants); track ("slot", i) carries an occupancy span per admission
    # with nested prefill-chunk spans; track ("engine", 0) carries the tick
    # phase spans (``self._phase``, also profiler annotations) plus
    # cow_copy / preempt / retrace instants.

    def _obs_submitted(self, rid: int, arrival_s: Optional[float] = None) -> None:
        """Queue wait and TTFT run from ``arrival_s`` (the request's due
        time on the ``perf_counter`` clock) when the caller knows it, else
        from now."""
        self._c_submitted.inc()
        t_submit = time.perf_counter() if arrival_s is None else arrival_s
        self._req_obs[rid] = {
            "t_submit": t_submit, "t_admit": None, "t_first": None,
            "t_last": None, "n_tokens": 0, "preempts": 0,
        }
        if self._tr:
            self._tr.begin(("request", rid), "queued", ts=t_submit)

    def _obs_admitted(self, rid: int, i: int) -> None:
        now = time.perf_counter()
        ro = self._req_obs.get(rid)
        if ro is not None and ro["t_admit"] is None:
            ro["t_admit"] = now
            self._h_queue.observe(now - ro["t_submit"])
        if self._tr:
            self._tr.end(("request", rid), ts=now)  # queued
            self._tr.begin(("request", rid), "prefill", ts=now)
            self._tr.begin(("slot", i), f"req{rid}", ts=now)

    def _obs_admitted_fork(self, rid: int, i: int, base_rid: int) -> None:
        now = time.perf_counter()
        ro = self._req_obs.get(rid)
        if ro is not None and ro["t_admit"] is None:
            ro["t_admit"] = now
            self._h_queue.observe(now - ro["t_submit"])
        if self._tr:
            self._tr.end(("request", rid), ts=now)  # queued
            self._tr.instant(("request", rid), "prefix_hit", ts=now,
                             args={"fork_of": base_rid})
            self._tr.begin(("request", rid), "decode", ts=now)
            self._tr.begin(("slot", i), f"req{rid}", ts=now)

    def _obs_token(self, rid: int, now: float) -> None:
        """One generated token: TTFT on the first, TPOT on the rest.  TPOT
        intervals broken by a preemption are dropped (t_last is reset) — the
        re-queue wait is preemption cost, not inter-token latency."""
        ro = self._req_obs.get(rid)
        if ro is None:
            return
        if ro["t_first"] is None:
            ro["t_first"] = now
            self._h_ttft.observe(now - ro["t_submit"])
        elif ro["t_last"] is not None:
            self._h_tpot.observe(now - ro["t_last"])
        ro["t_last"] = now
        ro["n_tokens"] += 1

    def _obs_first_token(self, rid: int) -> None:
        """Prefill finished and the first token was sampled: flip the
        request track from its prefill span to a decode span."""
        now = time.perf_counter()
        self._obs_token(rid, now)
        if self._tr:
            self._tr.end(("request", rid), ts=now)  # prefill
            self._tr.begin(("request", rid), "decode", ts=now)

    def _obs_completed(self, rid: int) -> None:
        now = time.perf_counter()
        ro = self._req_obs.pop(rid, None)
        if ro is not None:
            self._c_completed.inc()
            self._h_preempts.observe(ro["preempts"])
        if self._tr:
            self._tr.end(("request", rid), ts=now)  # decode
            self._tr.instant(("request", rid), "complete", ts=now)

    # ------------------------------------------------------------------
    def _clamped_budget(self, req: Request) -> int:
        # Budget clamp: the response plus at least one context token must fit
        # the per-sequence capacity (a budget >= capacity used to flip the
        # prompt-truncation index positive and keep the WRONG end of the
        # prompt — or nothing at all).
        return max(1, min(req.max_new_tokens, self.capacity - 1))

    def submit(self, req: Request) -> int:
        rid = self._next_id
        self._next_id += 1
        self.queue.append(_Pending(
            rid=rid, prompt=list(req.prompt), budget=self._clamped_budget(req),
            generated=[], prompt_len=len(req.prompt),
        ))
        self._obs_submitted(rid, req.arrival_s)
        self._admit()
        return rid

    def submit_n(self, req: Request, n: int) -> List[int]:
        """Submit ``n`` parallel samples of one prompt (one request id each).
        Under ``prefix_sharing`` the samples are page-aligned: the first is
        admitted normally and the rest fork it — block tables share ALL its
        prompt pages (including the partial boundary page) and per-slot
        ring/SSM/cross state is row-copied, so n samples cost one prompt's
        pages + one prefill until they diverge via copy-on-write.  Without
        sharing (or when slots/pages force staggered admission) each sample
        is served as an independent request — same tokens, no sharing."""
        if n < 1:
            raise ValueError(f"need n >= 1 samples, got {n}")
        budget = self._clamped_budget(req)
        rids: List[int] = []
        for j in range(n):
            rid = self._next_id
            self._next_id += 1
            self.queue.append(_Pending(
                rid=rid, prompt=list(req.prompt), budget=budget,
                generated=[], prompt_len=len(req.prompt),
                fork_of=rids[0] if j else -1,
            ))
            rids.append(rid)
            self._obs_submitted(rid, req.arrival_s)
        self._admit()
        return rids

    # ------------------------------------------------------------------
    def _fork_base_slot(self, item: _Pending) -> Optional[int]:
        """Slot index of ``item``'s fork base, iff the base is still exactly
        at its admission state: active, no decode tick since admission (its
        cache holds the prompt and nothing else — the single generated token
        is sampled but not yet written), prefill logits stashed.  Any other
        state means the boundary page already holds divergent tokens, so the
        fork must not share it and degrades to a normal admission."""
        if self.prefix is None or item.fork_of < 0:
            return None
        for b, s in enumerate(self.slots):
            if (s.active and not s.prefilling and s.request_id == item.fork_of
                    and len(s.generated) == 1 and s.prefill_logits is not None):
                return b
        return None

    def _fork_base_prefilling(self, item: _Pending) -> bool:
        """True while ``item``'s fork base is still mid-chunked-prefill: the
        base's pages are incomplete, so the fork can neither share them nor
        sensibly degrade (the base WILL reach its shareable admission state
        in a bounded number of ticks).  The queue head blocks — consistent
        with FIFO admission never skipping the head."""
        if self.prefix is None or item.fork_of < 0:
            return False
        return any(
            s.active and s.prefilling and s.request_id == item.fork_of
            for s in self.slots
        )

    def _admit_fork(self, i: int, b: int, item: _Pending) -> None:
        """Admit ``item`` into slot ``i`` as a page-aligned parallel sample of
        slot ``b``: share every page ``b`` holds (refcount + 1 each), point
        ``i``'s table at them, row-copy the per-slot leaves (window rings,
        SSM/LRU, cross), and draw the fork's first token from the base's
        stashed prefill logits.  Zero new pages, zero prefill compute; the
        first divergent append copy-on-writes the boundary page."""
        base = self.slots[b]
        self._obs_admitted_fork(item.rid, i, base.request_id)
        pages = [int(p) for p in self.tables.row(b) if p >= 0]
        self.pool.share(pages, owner=i)
        self.tables.copy_row(i, b)
        self._jit_calls_tick += 1
        self.caches = self._copy_slot(
            self.caches, jnp.asarray(b, jnp.int32), jnp.asarray(i, jnp.int32)
        )
        self._key, sub = jax.random.split(self._key)
        with self._phase("engine.wait"):
            # analysis: allow(host-cast) — the fork's first token must reach the Python scheduler (slot state, _cur_token) before the next tick
            first = int(sample(jnp.asarray(base.prefill_logits), sub,
                               temperature=self.temperature,
                               top_k=self.top_k, top_p=self.top_p)[0])
        self.slots[i] = SlotState(
            request_id=item.rid, pos=base.pos, generated=[first],
            budget=item.budget, active=True, admit_seq=self._admit_counter,
            prompt_len=item.prompt_len, prompt=item.prompt,
            prefill_logits=base.prefill_logits,
            seq=base.seq[:-1] + [first],
        )
        self._admit_counter += 1
        self._cur_token[i] = first
        self.prefix_hits += 1
        self.prefix_hit_tokens += base.pos
        self._c_prefix_hits.inc()
        self._c_prefix_toks.inc(base.pos)
        self._obs_token(item.rid, time.perf_counter())
        self._finish_if_done(i)

    def _admit(self) -> None:
        with self._phase("engine.admit"):
            self._admit_queue()

    def _admit_queue(self) -> None:
        """FIFO admission: fill free slots from the queue head.  In paged
        mode a request is only admitted when the pool has enough free pages
        for its prompt (admission by free-block count); the queue head blocks
        rather than being skipped, so long requests cannot starve.  Under
        prefix sharing, pages covering an indexed full-page prefix of the
        context are shared rather than allocated, and only the tail is
        prefilled into fresh pages.

        With ``prefill_mode="chunked"`` (default, paged) admission reserves
        the slot and ALL the prompt's pages, runs the first chunk of prefill
        synchronously, and leaves the slot ``prefilling`` — subsequent chunks
        run one budget per ``step()`` interleaved with decode.  The scatter
        mode (and the contiguous engine) prefill the whole context here."""
        while self.queue:
            free = [i for i, s in enumerate(self.slots) if not s.active]
            if not free:
                return
            i = free[0]
            item = self.queue[0]
            fork_base = self._fork_base_slot(item)
            if fork_base is not None:
                self.queue.pop(0)
                self._admit_fork(i, fork_base, item)
                continue
            if self._fork_base_prefilling(item):
                return  # the base reaches its shareable state in O(ticks)
            remaining = item.budget - len(item.generated)
            # keep the LAST (capacity - remaining) context tokens: the newest
            # prompt suffix, leaving exactly `remaining` cache tokens to decode
            keep = self.capacity - remaining
            ctx = (item.prompt + item.generated)[-keep:]
            shared: List[int] = []
            if self.paged:
                if self.prefix is not None:
                    # cap the match so at least one context token is left to
                    # prefill — last-token logits seed the first sample
                    shared = self.prefix.lookup(ctx, max_tokens=len(ctx) - 1)
                fresh = self.pool.alloc(
                    self.pool.pages_for(len(ctx)) - len(shared), owner=i)
                if fresh is None:
                    return  # wait for frees / completions
                if shared:
                    self.pool.share(shared, owner=i)
                    self.prefix_hits += 1
                    self.prefix_hit_tokens += len(shared) * self.page_size
                    self._c_prefix_hits.inc()
                    self._c_prefix_toks.inc(len(shared) * self.page_size)
                    if self._tr:
                        self._tr.instant(("request", item.rid), "prefix_hit",
                                         args={"tokens": len(shared) * self.page_size})
                self.tables.append(i, shared + fresh)
            self.queue.pop(0)
            self._obs_admitted(item.rid, i)
            if self.paged and self.prefill_mode in ("chunked", "batched"):
                # resumable admission: pages are reserved, compute is spread
                # over ticks.  On fully-paged archs shared-prefix positions
                # are never computed at all — their K/V is read from the
                # shared pages in place; ring/SSM archs recompute the prefix
                # (state rebuild) but still never write the shared pages.
                start = len(shared) * self.page_size if self._skip_shared_compute else 0
                self.prefill_tokens_skipped += start
                self._c_prefill_skip.inc(start)
                self.slots[i] = SlotState(
                    request_id=item.rid, pos=start, generated=list(item.generated),
                    budget=item.budget, active=True, admit_seq=self._admit_counter,
                    prompt_len=item.prompt_len, prompt=item.prompt,
                    prefilling=True, prefill_ctx=ctx, prefill_done=start,
                )
                self._admit_counter += 1
                if self.prefill_mode == "chunked":
                    self._advance_prefill(i)
                # batched mode: the slot joins the NEXT tick's single batched
                # prefill call — admission itself launches no compute
                continue
            toks = jnp.asarray(np.asarray(ctx, np.int32)[None])
            pos = jnp.arange(len(ctx), dtype=jnp.int32)[None]
            self._jit_calls_tick += 1
            if self.paged:
                # scatter oracle: full-context prefill into a temp contiguous
                # cache; shared-prefix positions are recomputed but their
                # writes are routed to the trash page — a shared page is
                # never written by an admission
                logits, self.caches = self._prefill(
                    self.params, toks, pos, jnp.asarray(i, jnp.int32), self.caches,
                    jnp.asarray(self.tables.row(i)),
                    jnp.asarray(len(shared) * self.page_size, jnp.int32),
                )
            else:
                logits, self.caches = self._prefill(
                    self.params, toks, pos, jnp.asarray(i, jnp.int32), self.caches
                )
            self.prefill_tokens_total += len(ctx)
            self._c_prefill_toks.inc(len(ctx))
            self._key, sub = jax.random.split(self._key)
            with self._phase("engine.wait"):
                # analysis: allow(host-cast) — admission's first sampled token feeds Python slot state; the sync is the admission boundary, not the tick
                first = int(sample(logits, sub, temperature=self.temperature,
                                   top_k=self.top_k, top_p=self.top_p)[0])
            # analysis: allow(host-asarray) — logits already host-synced by the cast above; the stash is what forks sample from without a recompute
            stash = np.asarray(logits) if self.prefix is not None else None
            self.slots[i] = SlotState(
                request_id=item.rid, pos=len(ctx), generated=item.generated + [first],
                budget=item.budget, active=True, admit_seq=self._admit_counter,
                prompt_len=item.prompt_len, prompt=item.prompt,
                prefill_logits=stash,
                seq=list(ctx) + [first],
            )
            self._admit_counter += 1
            self._cur_token[i] = first
            self._obs_first_token(item.rid)
            if self.prefix is not None:
                # register this context's full pages (shared entries are
                # already indexed and keep their mapping; fresh full pages
                # become shareable for future admissions)
                n_full = len(ctx) // self.page_size
                if n_full:
                    self.prefix.insert(ctx, [int(p) for p in self.tables.row(i)[:n_full]])
            self._finish_if_done(i)

    # ------------------------------------------------------------------
    def _advance_prefill(self, i: int) -> int:
        """Run slot ``i``'s chunked prefill up to the available budget — the
        current tick's shared ``_tick_budget`` when inside ``step()``, one
        full ``prefill_chunk`` when admission happens outside a tick
        (``submit()``) — and return the number of tokens computed.  Chunk
        boundaries are page-aligned (every non-final chunk fills whole pages
        and direct page writes never straddle a tick); the final chunk takes
        the remainder, and a leftover budget smaller than a page defers to
        the next tick rather than emitting an unaligned sub-page chunk
        (which would also cost a fresh XLA compilation per odd length).
        Full pages are registered in the prefix index PROGRESSIVELY, as soon
        as their chunk is written — an indexed page must already hold its
        K/V (another admission may share it the moment it appears), and
        indexing per chunk lets concurrent admissions share a long prompt's
        preamble while its tail is still being prefilled.  On the last chunk
        the returned logits seed the request's first sampled token."""
        slot = self.slots[i]
        done = 0
        # outside a tick (admission from submit()), one chunk budget total
        local_budget = self.prefill_chunk if self._tick_budget is None else None
        while slot.active and slot.prefilling:
            budget = self._tick_budget if local_budget is None else local_budget
            if budget <= 0:
                break
            ctx = slot.prefill_ctx
            start = slot.prefill_done
            end = min(len(ctx), start + budget)
            if end < len(ctx):
                aligned = end - (end % self.page_size)
                if aligned <= start:
                    break  # leftover budget < one page — resume next tick
                end = aligned
            toks = jnp.asarray(np.asarray(ctx[start:end], np.int32)[None])
            pos = jnp.arange(start, end, dtype=jnp.int32)[None]
            fn = self._prefill_chunk_cont if slot.prefill_started else self._prefill_chunk_first
            if self._tr:
                self._tr.begin(("slot", i), f"chunk[{start}:{end})",
                               args={"rid": slot.request_id})
            self._jit_calls_tick += 1
            logits, self.caches = fn(
                self.params, toks, pos, jnp.asarray(i, jnp.int32), self.caches,
                jnp.asarray(self.tables.row(i)),
            )
            if self._tr:
                self._tr.end(("slot", i))
            slot.prefill_started = True
            n = end - start
            done += n
            self.prefill_tokens_total += n
            self._c_prefill_toks.inc(n)
            if local_budget is None:
                self._tick_budget -= n
                self._tick_prefill_done += n
            else:
                local_budget -= n
            slot.prefill_done = slot.pos = end
            if self.prefix is not None:
                # progressive registration: every page this chunk completed
                # is shareable NOW (existing mappings — the shared prefix
                # itself — are kept, first writer wins)
                n_full = end // self.page_size
                if n_full:
                    self.prefix.insert(ctx, [int(p) for p in self.tables.row(i)[:n_full]])
            if end == len(ctx):
                self._key, sub = jax.random.split(self._key)
                with self._phase("engine.wait"):
                    # analysis: allow(host-cast) — last-chunk logits seed the request's first token; it must land in Python slot state this tick
                    first = int(sample(logits, sub, temperature=self.temperature,
                                       top_k=self.top_k, top_p=self.top_p)[0])
                slot.prefilling = False
                slot.prefill_ctx = []
                slot.generated = slot.generated + [first]
                slot.seq = list(ctx) + [first]
                # analysis: allow(host-asarray) — already synced by the cast above; stashed for fork admission
                slot.prefill_logits = np.asarray(logits) if self.prefix is not None else None
                self._cur_token[i] = first
                self._obs_first_token(slot.request_id)
                self._finish_if_done(i)
                if self.queue:
                    # a fork blocked on THIS slot's prefill can now share it
                    self._admit()
        return done

    def _prefill_tick(self) -> None:
        """One tick's worth of admission prefill: advance prefilling slots in
        admission order against the tick's shared ``_tick_budget`` (set by
        ``step()``, spanning the WHOLE tick so completions that cascade into
        fresh admissions — during this pass or the decode phase — draw their
        first chunk from the same budget)."""
        order = sorted(
            (i for i, s in enumerate(self.slots) if s.active and s.prefilling),
            key=lambda i: self.slots[i].admit_seq,
        )
        for i in order:
            if self._tick_budget <= 0:
                break
            self._advance_prefill(i)

    def _prefill_tick_batched(self) -> None:
        """One tick's admission prefill as a SINGLE jitted call: every
        mid-prefill slot advances one chunk (up to ``prefill_chunk`` tokens
        each, page-aligned boundaries — the same per-slot chunk arithmetic as
        ``_advance_prefill``) through the fixed-shape batched entry.  Rows
        without a chunk this tick ride along inactive: all--1 table rows
        (pool writes trash-routed) and a masked per-slot-leaf merge inside
        the model entry keep their state untouched.  Finalization — first
        sampled token, progressive prefix-index registration, completion /
        cascaded admission — replays ``_advance_prefill``'s final-chunk
        semantics per finishing row, in admission order."""
        order = sorted(
            (i for i, s in enumerate(self.slots) if s.active and s.prefilling),
            key=lambda i: self.slots[i].admit_seq,
        )
        plan: Dict[int, tuple] = {}
        for i in order:
            slot = self.slots[i]
            ctx, start = slot.prefill_ctx, slot.prefill_done
            end = min(len(ctx), start + self.prefill_chunk)
            if end < len(ctx):
                end -= end % self.page_size
                if end <= start:
                    continue  # < one page of room — resume next tick
            plan[i] = (start, end)
        if not plan:
            return
        S, C = self.n_slots, self.prefill_chunk
        tokens = np.zeros((S, C), np.int32)
        positions = np.full((S, C), -1, np.int32)
        reset = np.zeros((S,), bool)
        active = np.zeros((S,), bool)
        last_idx = np.zeros((S,), np.int32)
        tbl = np.full((S, self.max_pages), -1, np.int32)
        for i, (start, end) in plan.items():
            slot = self.slots[i]
            n = end - start
            tokens[i, :n] = np.asarray(slot.prefill_ctx[start:end], np.int32)
            positions[i, :n] = np.arange(start, end, dtype=np.int32)
            reset[i] = not slot.prefill_started
            active[i] = True
            last_idx[i] = n - 1
            tbl[i] = self.tables.row(i)
            if self._tr:
                self._tr.begin(("slot", i), f"chunk[{start}:{end})",
                               args={"rid": slot.request_id})
        self._jit_calls_tick += 1
        self._batched_occ_tick = len(plan) / S
        logits, self.caches = self._prefill_chunk_batched(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(reset), jnp.asarray(active), jnp.asarray(last_idx),
            self.caches, jnp.asarray(tbl),
        )
        if self._tr:
            for i in plan:
                self._tr.end(("slot", i))
        logits_host: Optional[np.ndarray] = None
        for i, (start, end) in plan.items():  # dict preserves admission order
            slot = self.slots[i]
            n = end - start
            self._tick_prefill_done += n
            self.prefill_tokens_total += n
            self._c_prefill_toks.inc(n)
            slot.prefill_started = True
            slot.prefill_done = slot.pos = end
            ctx = slot.prefill_ctx
            if self.prefix is not None:
                # progressive registration, same as the per-slot path: pages
                # this chunk completed are shareable NOW
                n_full = end // self.page_size
                if n_full:
                    self.prefix.insert(ctx, [int(p) for p in self.tables.row(i)[:n_full]])
            if end == len(ctx):
                if logits_host is None:
                    with self._phase("engine.wait"):
                        # analysis: allow(host-asarray) — ONE sync serves every row finishing its prompt this tick; their first tokens must land in Python slot state
                        logits_host = np.asarray(logits)
                row = logits_host[i : i + 1]
                self._key, sub = jax.random.split(self._key)
                with self._phase("engine.wait"):
                    # analysis: allow(host-cast) — the finishing row's first sampled token feeds Python slot state (eos/budget/fork decisions)
                    first = int(sample(jnp.asarray(row), sub, temperature=self.temperature,
                                       top_k=self.top_k, top_p=self.top_p)[0])
                slot.prefilling = False
                slot.prefill_ctx = []
                slot.generated = slot.generated + [first]
                slot.seq = list(ctx) + [first]
                slot.prefill_logits = row.copy() if self.prefix is not None else None
                self._cur_token[i] = first
                self._obs_first_token(slot.request_id)
                self._finish_if_done(i)
        if self.queue:
            # a fork blocked on a just-finished base's prefill can now share it
            self._admit()

    def _end_tick_prefill(self) -> int:
        """Close the tick's prefill budget; returns tokens spent this tick."""
        if self._tick_budget is None:
            return 0
        done = self._tick_prefill_done
        self._tick_budget = None
        self._tick_prefill_done = 0
        return done

    def _release_slot(self, i: int) -> None:
        if self._tr:
            self._tr.end(("slot", i))  # occupancy span opened at admission
        if self.paged:
            # decref everything the slot holds; only pages whose refcount hit
            # zero are actually freed — pages another slot still references
            # stay live, mapped, and (if full) indexed for future sharing
            freed = self.pool.release(i)
            self.tables.reset(i)
            if self.drafter is not None:
                # lazy re-prefill covers the next occupant (or this request's
                # re-admission after preemption) at its first speculative tick
                self.drafter.invalidate(i)
            if freed:
                if self.prefix is not None:
                    self.prefix.evict_pages(freed)
                # invalidate the recycled pages' positions device-side, or a
                # later owner would see the previous occupant's stale K/V
                mask = np.zeros((self.n_pages + 1,), bool)
                mask[freed] = True
                self._jit_calls_tick += 1
                self.caches = self._reset_pages(self.caches, jnp.asarray(mask))
        self.slots[i] = SlotState()

    def _finish_if_done(self, i: int) -> None:
        slot = self.slots[i]
        if not slot.active or slot.prefilling:
            return
        hit_eos = self.eos_id >= 0 and slot.generated and slot.generated[-1] == self.eos_id
        if len(slot.generated) >= slot.budget or hit_eos:
            gen = slot.generated
            if hit_eos:
                gen = gen[:-1]
            self.done[slot.request_id] = Response(tokens=gen, prompt_len=slot.prompt_len)
            self._obs_completed(slot.request_id)
            self._release_slot(i)
            self._admit()

    def _preempt(self, i: int) -> None:
        """Push slot ``i`` back to the queue head and free its pages.  The
        request resumes later by re-prefilling prompt + generated-so-far, so
        greedy decoding continues token-exact."""
        slot = self.slots[i]
        rid = slot.request_id
        self.queue.insert(0, _Pending(
            rid=rid, prompt=slot.prompt, budget=slot.budget,
            generated=slot.generated, prompt_len=slot.prompt_len,
        ))
        ro = self._req_obs.get(rid)
        if ro is not None:
            ro["preempts"] += 1
            ro["t_last"] = None  # don't count re-queue wait as TPOT
        if self._tr:
            now = time.perf_counter()
            self._tr.instant(("request", rid), "preempted", ts=now)
            self._tr.end(("request", rid), ts=now)  # decode (or prefill) span
            self._tr.begin(("request", rid), "queued", ts=now)
            self._tr.instant(("engine", 0), "preempt", ts=now,
                             args={"rid": rid, "slot": i})
        self._release_slot(i)
        self.preemptions += 1
        self._c_preempt.inc()

    def _youngest_active(self) -> int:
        return max(
            (j for j, s in enumerate(self.slots) if s.active),
            key=lambda j: self.slots[j].admit_seq,
        )

    def _ensure_pages(self) -> None:
        """Pre-tick page discipline, per active slot in admission order:

        1. **Lazy table growth** — map a page for the slot's write position;
           when the pool is dry the *youngest* active slot is preempted
           (LIFO — the request with the least sunk prefill/decode work
           re-queues).
        2. **Copy-on-write** — if the write-position page has refcount > 1
           (a prefix/fork sharer), fork it: allocate a private page, copy the
           device contents, remap this slot's table entry, decref the
           original.  After this pass every DECODING slot's write page has
           refcount 1, which is the invariant that makes shared pages
           read-only under decode.

        Mid-prefill slots are skipped: their pages were all reserved at
        admission (no growth needed) and chunks write only freshly-allocated
        refcount-1 pages (shared prefix pages are page-aligned and strictly
        before the first chunk, so no CoW either)."""
        order = sorted(
            (i for i, s in enumerate(self.slots) if s.active and not s.prefilling),
            key=lambda i: self.slots[i].admit_seq,
        )
        for i in order:
            slot = self.slots[i]
            while slot.active and self.tables.n_mapped(i) <= slot.pos // self.page_size:
                got = self.pool.alloc(1, owner=i)
                if got is not None:
                    self.tables.append(i, got)
                    continue
                victim = self._youngest_active()
                self._preempt(victim)
                if victim == i:
                    break  # this slot itself re-queued; stop growing it
            while self.slots[i].active:
                entry = slot.pos // self.page_size
                page = int(self.tables.row(i)[entry])
                if self.pool.refcount(page) <= 1:
                    break
                new = self.pool.fork(page, i)
                if new is None:
                    victim = self._youngest_active()
                    self._preempt(victim)
                    if victim == i:
                        break  # re-queued; a sharer keeps the page alive
                    continue  # a preemption may even have dropped the refcount
                self._jit_calls_tick += 1
                self.caches = self._copy_page(
                    self.caches, jnp.asarray(page, jnp.int32), jnp.asarray(new, jnp.int32)
                )
                self.tables.set_entry(i, entry, new)
                self.cow_copies += 1
                self._c_cow.inc()
                if self._tr:
                    self._tr.instant(("engine", 0), "cow_copy",
                                     args={"slot": i, "src": page, "dst": new})

    # -- speculative decoding (serving/spec.py holds the drafter) --------
    def _spec_plan_pages(self, decoding) -> Dict[int, dict]:
        """Per-slot speculation plan: window size ``k`` and the page run the
        verify pass writes.  The window covers positions ``p .. p + k`` (the
        unwritten current token plus ``k`` drafted ones), i.e. table entries
        ``e0 = p // ps`` through ``e1 = (p + k) // ps``:

          * the BOUNDARY entry ``e0`` (only when ``p`` is mid-page) is the
            partially-filled tail page.  If it is shared (refcount > 1) the
            plan forks it — fresh page + device copy — and the verify table
            points at the fork, so the shared base is never written (commit
            = refcount handoff, ``KVBlockPool.commit_fork_run``).  A private
            (refcount 1) boundary is written in place: the boundary entry
            ALWAYS commits (at least one token is emitted per window), so
            in-place writes are never rolled back — stale tail entries past
            the accepted point are handled by ``_spec_reset_tail``;
          * entries beyond ``e0`` are fresh pages, allocated all-or-nothing
            with the same preempt-youngest discipline as ``_ensure_pages``
            (which spec mode replaces: the plan subsumes lazy growth + CoW).
            On commit, entries up to the last accepted position's page join
            the block table; the rest roll back via ``drop_fork_run``.

        A dry pool preempts the youngest active slot — possibly one already
        planned (its plan is discarded below; ``_release_slot`` already freed
        its window pages) or the slot being planned (skipped)."""
        ps = self.page_size
        order = sorted((i for i in range(self.n_slots) if decoding[i]),
                       key=lambda i: self.slots[i].admit_seq)
        plans: Dict[int, dict] = {}
        for i in order:
            slot = self.slots[i]
            if not slot.active or slot.prefilling:
                continue  # preempted by an earlier plan's allocation
            p = slot.pos
            remaining = slot.budget - len(slot.generated)
            k = max(0, min(self.spec_k, remaining - 1, self.capacity - 1 - p))
            e0, e1 = p // ps, (p + k) // ps
            while True:
                slot = self.slots[i]
                if not slot.active or slot.prefilling:
                    break  # this slot itself was preempted; no plan
                boundary = int(self.tables.row(i)[e0]) if p % ps else -1
                fork_boundary = boundary >= 0 and self.pool.refcount(boundary) > 1
                need = (e1 - e0) + (0 if p % ps else 1) + (1 if fork_boundary else 0)
                got = self.pool.alloc(need, owner=i)
                if got is not None:
                    window: Dict[int, int] = {}
                    fork = -1
                    if fork_boundary:
                        fork = got.pop()
                        self._jit_calls_tick += 1
                        self.caches = self._copy_page(
                            self.caches, jnp.asarray(boundary, jnp.int32),
                            jnp.asarray(fork, jnp.int32))
                        self.cow_copies += 1
                        self._c_cow.inc()
                        window[e0] = fork
                    elif boundary >= 0:
                        window[e0] = boundary  # private: write in place
                    for e, pg in zip((e for e in range(e0, e1 + 1)
                                      if e not in window), got):
                        window[e] = pg
                    plans[i] = dict(
                        rid=slot.request_id, k=k, p=p, e0=e0, e1=e1,
                        window=window,
                        boundary_base=boundary if fork_boundary else -1,
                        boundary_fork=fork,
                    )
                    break
                victim = self._youngest_active()
                self._preempt(victim)
                if victim == i:
                    break
        # drop plans whose slot was preempted by a later allocation — its
        # window pages were freed (and device-reset) by _release_slot
        return {i: pl for i, pl in plans.items()
                if self.slots[i].active and not self.slots[i].prefilling
                and self.slots[i].request_id == pl["rid"]}

    def _spec_decode_tick(self, decoding) -> int:
        """One speculative decode tick: draft ``k`` tokens per decoding slot,
        verify every slot's ``k + 1`` window positions in ONE batched target
        pass over CoW-forked tail pages, commit the longest argmax-agreeing
        prefix (plus the target's own token at the first disagreement) by
        refcount handoff, and roll back the rejected suffix by dropping fork
        pages.  Token-exact vs the non-speculative greedy engine by
        construction (tests/test_spec.py).  Returns #tokens emitted.

        Ordering within the tick (each phase one jitted call at most):
        plan (may preempt) -> drafter sync/propose -> verify -> host
        accept/commit bookkeeping (pages, tables, slot state) -> committed
        recurrent-state pass (non-fully-paged archs) -> batched page-tail
        invalidation + rollback page resets -> completions (which may cascade
        admissions; they must run AFTER the commit pass or a fresh occupant's
        first chunk could be clobbered)."""
        plans = self._spec_plan_pages(decoding)
        if not plans:
            return 0
        ps = self.page_size
        S, C = self.n_slots, self.spec_k + 1

        # --- draft: lazily (re)sync the drafter, then ONE propose scan
        with self._phase("spec_draft"):
            need_draft = []
            for i, pl in plans.items():
                slot = self.slots[i]
                if pl["k"] == 0:
                    continue  # window is just the current token; nothing to draft
                if self.drafter.needs_sync(i, slot.pos):
                    if self.drafter.next_pos[i] >= 0:
                        self._c_spec_resyncs.inc()
                        self._spec_tick_m["resyncs"] = \
                            self._spec_tick_m.get("resyncs", 0) + 1
                    self._jit_calls_tick += 1
                    self.drafter.sync(i, slot.seq, slot.pos)
                forced = slot.seq[int(self.drafter.next_pos[i]):slot.pos + 1]
                need_draft.append((i, forced, pl["k"]))
            if need_draft:
                self._jit_calls_tick += 1
                proposals = self.drafter.propose(need_draft)
            else:
                proposals = {}
            for i, pl in plans.items():
                pl["proposal"] = proposals.get(i, [])

        # --- verify: all windows, one batched pass over the fork tables
        with self._phase("spec_verify"):
            tokens = np.zeros((S, C), np.int32)
            positions = np.full((S, C), -1, np.int32)
            active = np.zeros((S,), bool)
            tbl = np.full((S, self.max_pages), -1, np.int32)
            for i, pl in plans.items():
                k, p = pl["k"], pl["p"]
                tokens[i, 0] = self._cur_token[i]
                tokens[i, 1:1 + k] = pl["proposal"]
                positions[i, :k + 1] = np.arange(p, p + k + 1, dtype=np.int32)
                active[i] = True
                row = np.array(self.tables.row(i))
                for e, pg in pl["window"].items():
                    row[e] = pg
                tbl[i] = row
            self._jit_calls_tick += 1
            greedy_dev, self.caches = self._verify(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(active), self.caches, jnp.asarray(tbl),
            )
            with self._phase("engine.wait"):
                # analysis: allow(host-asarray) — THE per-tick sync: the target's greedy tokens drive accept/commit/rollback decisions on the host
                greedy = np.asarray(greedy_dev)

        # --- accept + commit/rollback bookkeeping (host side, in admission
        # order; greedy[i, j] is the target's token for position p + j + 1)
        with self._phase("spec_commit"):
            tail_pages = np.full((S,), -1, np.int32)
            tail_offs = np.zeros((S,), np.int32)
            reset_mask = np.zeros((self.n_pages + 1,), bool)
            any_reset = False
            commit_rows = []
            emitted_total = accepted_total = drafted_total = 0
            t_tok = time.perf_counter()
            for i, pl in plans.items():
                slot = self.slots[i]
                k, p = pl["k"], pl["p"]
                a = accept_length(pl["proposal"], greedy[i])
                # accepted drafts, then the target's own token at the first
                # disagreement (or the bonus token after a full accept)
                appended = [int(t) for t in pl["proposal"][:a]] + [int(greedy[i, a])]
                if self.eos_id >= 0 and self.eos_id in appended:
                    appended = appended[:appended.index(self.eos_id) + 1]
                n = len(appended)  # 1 <= n <= k + 1 <= remaining budget
                # pages: entries up to eb = page of the last ACCEPTED position
                # commit; later window entries roll back.  n >= 1 makes eb >= e0
                # always — the boundary entry commits on every outcome.
                eb = (p + n - 1) // ps
                if pl["boundary_fork"] >= 0:
                    freed = self.pool.commit_fork_run([pl["boundary_base"]], i)
                    self.tables.set_entry(i, pl["e0"], pl["boundary_fork"])
                    if freed:  # a sharer departed mid-tick and left us the base
                        if self.prefix is not None:
                            self.prefix.evict_pages(freed)
                        reset_mask[freed] = True
                        any_reset = True
                grow = [pl["window"][e]
                        for e in range(self.tables.n_mapped(i), eb + 1)]
                if grow:
                    self.tables.append(i, grow)
                rollback = [pl["window"][e] for e in range(eb + 1, pl["e1"] + 1)]
                if rollback:
                    freed = self.pool.drop_fork_run(rollback, i)
                    reset_mask[freed] = True
                    any_reset = True
                    self._c_spec_rollback_pages.inc(len(freed))
                self._c_spec_commit_pages.inc(len(grow))
                # position p + n - 1 is the last VALID write in page eb; verify
                # writes beyond it (rejected drafts) are invalidated in one
                # batched pass below, restoring the `already`-guard invariant
                tail_pages[i] = int(self.tables.row(i)[eb])
                tail_offs[i] = (p + n - 1) % ps + 1
                # slot state: appended[-1] is the new sampled-but-unwritten token
                slot.pos = p + n
                slot.generated.extend(appended)
                slot.seq.extend(appended)
                slot.prefill_logits = None
                self._cur_token[i] = appended[-1]
                if self.drafter.after_commit(i, p, k, a == k, slot.pos):
                    self._c_spec_resyncs.inc()
                    self._spec_tick_m["resyncs"] = \
                        self._spec_tick_m.get("resyncs", 0) + 1
                emitted_total += n
                accepted_total += a
                drafted_total += k
                self._c_spec_verifies.inc()
                self._c_spec_drafted.inc(k)
                self._c_spec_accepted.inc(a)
                if k:
                    self._h_accept.observe(a / k)
                self._h_tok_verify.observe(float(n))
                if self._tr:
                    self._tr.instant(("request", slot.request_id), "spec_commit",
                                     ts=t_tok, args={"drafted": k, "accepted": a,
                                                     "emitted": n})
                commit_rows.append((i, pl, appended))

            # --- committed recurrent-state pass (window rings / SSM / LRU /
            # conv): re-run the accepted tokens through the batched chunk entry
            # so per-slot leaves advance; pool writes are `already`-trash-routed
            if self._spec_commit is not None:
                tokens2 = np.zeros((S, C), np.int32)
                positions2 = np.full((S, C), -1, np.int32)
                active2 = np.zeros((S,), bool)
                last2 = np.zeros((S,), np.int32)
                tbl2 = np.full((S, self.max_pages), -1, np.int32)
                for i, pl, appended in commit_rows:
                    slot = self.slots[i]
                    n, p = len(appended), pl["p"]
                    tokens2[i, :n] = np.asarray(slot.seq[p:p + n], np.int32)
                    positions2[i, :n] = np.arange(p, p + n, dtype=np.int32)
                    active2[i] = True
                    last2[i] = n - 1
                    tbl2[i] = self.tables.row(i)
                self._jit_calls_tick += 1
                _, self.caches = self._spec_commit(
                    self.params, jnp.asarray(tokens2), jnp.asarray(positions2),
                    jnp.asarray(np.zeros((S,), bool)), jnp.asarray(active2),
                    jnp.asarray(last2), self.caches, jnp.asarray(tbl2),
                )

            # --- device-side invalidation: committed-page tails (always — one
            # fixed-shape call per commit tick) and rollback-freed pages
            self._jit_calls_tick += 1
            self.caches = self._spec_reset_tail(
                self.caches, jnp.asarray(tail_pages), jnp.asarray(tail_offs))
            if any_reset:
                self._jit_calls_tick += 1
                self.caches = self._reset_pages(self.caches, jnp.asarray(reset_mask))

        # --- completions last: _finish_if_done may release the slot and
        # cascade an admission into it (whose first chunk must not be
        # clobbered by the commit pass above).  Emitted tokens share one
        # timestamp — bursty TPOT is the truth of speculative decoding.
        for i, pl, appended in commit_rows:
            slot = self.slots[i]
            if slot.request_id != pl["rid"]:
                continue  # released + re-admitted earlier in this loop
            for _ in appended:
                self._obs_token(slot.request_id, t_tok)
            self._finish_if_done(i)
        self._spec_tick_m.update(
            windows=len(commit_rows), drafted=drafted_total,
            accepted=accepted_total, emitted=emitted_total)
        return emitted_total

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine tick: at most one chunk-budget of admission prefill
        (chunked mode), then one decode step over every active slot that is
        not mid-prefill.  Returns #active slots (decoding + prefilling), so
        callers keep ticking while long admissions are still being written.
        Per-tick scheduler telemetry lands in ``last_metrics`` /
        ``metrics_log`` (active slots, prefill/decode token counts,
        free/shared pages, CoW copies, tok/s, preemptions, routed experts).

        The tick and its phases are spans (``engine.tick`` around
        ``engine.prefill``, ``engine.pages``, ``engine.decode``,
        ``engine.sample``, ``engine.commit``, ``engine.record``, with
        ``engine.admit`` wherever admission runs and ``engine.wait`` inside
        whichever phase blocks on the device), so a profiler trace splits
        the host's tick by phase on the device's clock."""
        with self._phase("engine.tick"):
            return self._run_tick()

    def _fence(self) -> None:
        """End-of-tick fence: the donated cache updates are still in flight
        after the token readback; without it ``tick_s`` under-reports the
        device time the tick consumed, and a caller stamping tokens at the
        tick's return would stamp them early."""
        with self._phase("engine.wait"):
            # analysis: allow(block-sync) — deliberate end-of-tick fence for tick latency accounting
            jax.block_until_ready(self.caches)

    def _run_tick(self) -> int:
        t0 = time.perf_counter()
        if self.paged and self.prefill_mode in ("chunked", "batched"):
            # bounded head-of-line blocking: decode (below) runs every tick,
            # delayed by at most this one chunk of prefill compute — the
            # budget spans the whole tick, so admissions cascaded from
            # completions draw from it too.  (Batched mode budgets per ROW:
            # every mid-prefill slot advances one chunk in the single batched
            # call, so the tick still issues at most one prefill dispatch.)
            self._tick_budget = self.prefill_chunk
        if not any(s.active for s in self.slots):
            self._admit()
            if not any(s.active for s in self.slots):
                self._end_tick_prefill()
                return 0
        if self._tick_budget is not None:
            with self._phase("engine.prefill"):
                if self.prefill_mode == "batched":
                    self._prefill_tick_batched()
                else:
                    self._prefill_tick()
        if self.paged and self.drafter is None:
            # spec mode skips this: _spec_plan_pages subsumes lazy growth and
            # CoW for the whole k+1 window, fork-first instead of copy-first
            with self._phase("engine.pages"):
                self._ensure_pages()
        # rows eligible to decode this tick — mid-prefill slots are excluded,
        # and their table rows are masked out of the decode step so its pool
        # writes land in the trash page, never in a half-written prompt page
        decoding = np.asarray([s.active and not s.prefilling for s in self.slots])
        n_active = int(sum(s.active for s in self.slots))
        if not decoding.any():
            with self._phase("engine.commit"):
                prefill_toks = self._end_tick_prefill()
                if prefill_toks:
                    self._fence()
            if n_active or prefill_toks:
                with self._phase("engine.record"):
                    self._record_metrics(0, time.perf_counter() - t0, prefill_toks, n_active)
            return n_active
        if self.drafter is not None:
            # speculative path: the whole draft/verify/commit tick replaces
            # the one-token decode step below (same tick telemetry shape)
            n_decoded = self._spec_decode_tick(decoding)
            with self._phase("engine.commit"):
                prefill_toks = self._end_tick_prefill()
                self._fence()
            with self._phase("engine.record"):
                self._record_metrics(n_decoded, time.perf_counter() - t0, prefill_toks, n_active)
            return n_active
        with self._phase("engine.decode"):
            positions = np.asarray([s.pos if s.active else 0 for s in self.slots], np.int32)
            tokens = jnp.asarray(self._cur_token[:, None])
            self._jit_calls_tick += 1
            if self.paged:
                tbl = np.where(decoding[:, None], self.tables.table, -1)
                logits, self.caches, tel = self._decode(
                    self.params, tokens, jnp.asarray(positions), jnp.asarray(decoding),
                    self.caches, jnp.asarray(tbl),
                )
            else:
                logits, self.caches, tel = self._decode(
                    self.params, tokens, jnp.asarray(positions), jnp.asarray(decoding), self.caches
                )
        with self._phase("engine.sample"):
            self._key, sub = jax.random.split(self._key)
            nxt_dev = sample(logits, sub, temperature=self.temperature,
                             top_k=self.top_k, top_p=self.top_p)
            with self._phase("engine.wait"):
                # analysis: allow(host-asarray) — THE per-tick sync: sampled tokens drive eos/budget/admission decisions on the host; the dispatch's counters ride in the same transfer
                nxt, counters = jax.device_get((nxt_dev, tel["counters"]))
        n_decoded = int(decoding.sum())
        t_tok = time.perf_counter()
        with self._phase("engine.commit"):
            for i, slot in enumerate(self.slots):
                # Gate on the PRE-decode snapshot, not slot.active: a
                # completion at row < i can trigger _admit into free row i
                # mid-loop, and that fresh slot must not consume nxt[i] — its
                # logits row was computed while the row was inactive (or
                # still prefilling).
                if not decoding[i]:
                    continue
                slot.pos += 1
                slot.generated.append(int(nxt[i]))
                slot.seq.append(int(nxt[i]))
                # the stashed admission logits are only consumable by a fork
                # BEFORE the base's first decode tick — drop the dead copy
                slot.prefill_logits = None
                self._cur_token[i] = int(nxt[i])
                self._obs_token(slot.request_id, t_tok)
                self._finish_if_done(i)
            prefill_toks = self._end_tick_prefill()
            self._fence()
        t1 = time.perf_counter()
        with self._phase("engine.record"):
            routing_m = summarize_routing(tel["routing"]) if tel.get("routing") else None
            self._record_metrics(n_decoded, t1 - t0, prefill_toks, n_active,
                                 routing=routing_m, counters=counters)
        return n_active

    def _record_metrics(self, n_decoded: int, dt: float, prefill_toks: int = 0,
                        n_active: Optional[int] = None, *,
                        routing: Optional[dict] = None,
                        counters: Optional[dict] = None) -> None:
        retraces = self.obs.watchdog.tick()
        if retraces:
            self._c_retraces.inc(retraces)
            if self._tr and self.obs.watchdog.steady_retraces:
                self._tr.instant(("engine", 0), "retrace",
                                 args={"compiles": retraces})
        self._tick += 1
        self._h_tick.observe(dt)
        if n_decoded:
            self._c_decode_toks.inc(n_decoded)
        active = n_decoded if n_active is None else n_active
        self._g_active.set(active)
        self._g_queue.set(len(self.queue))
        m = {
            "tick": self._tick,
            # all slots holding pages, INCLUDING mid-prefill ones; the decode
            # participation count is tokens_this_tick
            "active_slots": active,
            "queue_depth": len(self.queue),
            "tokens_this_tick": n_decoded,
            "tok_per_s": round(n_decoded / max(dt, 1e-9), 2),
            "tick_s": round(dt, 6),
            "retraces": retraces,
            "preemptions": self.preemptions,
            # jitted-function invocations attributed to this tick (including
            # submit-time admissions since the last record) — in batched mode
            # the steady-state fused tick holds this at <= 2 primary calls
            "jitted_calls": self._jit_calls_tick,
        }
        self._g_jit_calls.set(self._jit_calls_tick)
        self._jit_calls_tick = 0
        if self.paged and self.prefill_mode == "batched":
            m["batched_prefill_occupancy"] = round(self._batched_occ_tick, 4)
            self._g_batch_occ.set(round(self._batched_occ_tick, 4))
            self._batched_occ_tick = 0.0
        for k, v in (counters or {}).items():
            # per MoE layer, in layer order: ``experts_routed``, the experts
            # the decode step's dispatch gave at least one row (padding rows
            # included) — whose weights the step read; on an EP mesh
            # ``ep_rows``, the rows each rank's experts received
            m[k] = v.tolist()
        if routing is not None:
            self._g_r_drop.set(routing["dropped_frac"])
            self._g_r_ent.set(routing["entropy"])
            self._g_r_imb.set(routing["imbalance"])
            m["routing"] = {k: routing[k] for k in
                            ("moe_layers", "dropped_frac", "entropy", "imbalance")}
        if self.paged:
            self._g_free_pages.set(self.pool.free_count)
            occ = self.pool.occupancy
            self._g_occupancy.set(round(occ, 4))
            self._g_peak_occ.set(round(max(occ, self._g_peak_occ.value or 0.0), 4))
            self._g_shared.set(self.pool.shared_count)
            m["prefill_tokens"] = prefill_toks
            m["free_pages"] = self.pool.free_count
            m["page_occupancy"] = round(self.pool.occupancy, 4)
            m["shared_pages"] = self.pool.shared_count
            m["cow_copies"] = self.cow_copies
            if self.prefix is not None:
                m["prefix_hits"] = self.prefix_hits
                m["prefix_hit_tokens"] = self.prefix_hit_tokens
        if self.drafter is not None and self._spec_tick_m:
            m["spec"] = self._spec_tick_m
            self._spec_tick_m = {}
        self.last_metrics = m
        self.metrics_log.append(m)
        if len(self.metrics_log) > self._metrics_cap:
            del self.metrics_log[: -self._metrics_cap]

    def run_until_done(self, max_ticks: int = 10_000) -> Dict[int, Response]:
        for _ in range(max_ticks):
            if self.step() == 0 and not self.queue:
                break
        return dict(self.done)
