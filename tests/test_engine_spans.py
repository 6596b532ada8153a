"""The engine's tick phases as profiler spans, and the decode step's
routed-expert counter.

* Phase spans: a tiny paged ``ContinuousEngine`` runs a few ticks under
  ``jax.profiler`` with the Python tracer off (host events are then only
  annotations and JAX's own), and the trace is reduced with the
  benchmark's own ``bench.trace.extract``: every ``engine.tick`` holds its
  decode, sample, commit and record phases, every ``engine.wait`` lies
  inside a phase, and admission ticks hold ``engine.prefill``.  With the
  default ``Obs()`` the Chrome tracer records nothing.
* ``experts_routed``: on every decode tick, per MoE layer, the number of
  experts the dispatch gave a row equals the number with a nonzero count in
  the bincount of the same gating's expert choices, recomputed through the
  routing-stats path of the same decode step, on the grouped and the
  einsum dispatch.
* Queue wait and TTFT run from a request's due time when it carries one.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from repro.core.prmoe import nlg_moe  # noqa: E402
from repro.models.model import init_params, paged_ragged_decode_step  # noqa: E402
from repro.obs import Obs  # noqa: E402
from repro.serving.continuous import ContinuousEngine  # noqa: E402
from repro.serving.engine import Request  # noqa: E402

PHASES = ("engine.admit", "engine.prefill", "engine.pages", "engine.decode",
          "engine.sample", "engine.commit", "engine.record")


def _cfg(impl: str):
    # 6 layers, dense and MoE in turn: a 4-expert MoE layer, then two
    # 8-expert ones in a second segment, each with the residual MLP (PR-MoE)
    return nlg_moe("spans-tiny", 6, 64, 4, [4, 8], residual=True, vocab=256).replace(moe_impl=impl)


@pytest.fixture(scope="module")
def grouped():
    cfg = _cfg("grouped")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    return ContinuousEngine(cfg, params, slots=3, capacity=64, paged=True, page_size=8,
                            prefill_chunk=16, prefill_mode="batched", **kw)


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(grouped, tmp_path_factory):
    """A warm engine's ticks under the profiler, as the benchmark's harness
    records them: the extract's host events and the engine's tick log."""
    cfg, params = grouped
    eng = _engine(cfg, params)
    eng.submit(Request(prompt=list(range(1, 20)), max_new_tokens=3))
    eng.run_until_done()  # compile outside the trace
    logdir = tmp_path_factory.mktemp("spans")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        # prompts within one prefill chunk: every tick that admits also
        # decodes, and a third request is admitted while the first two run
        eng.submit(Request(prompt=list(range(1, 14)), max_new_tokens=8))
        eng.submit(Request(prompt=list(range(3, 19)), max_new_tokens=9))
        for k in range(6):
            if k == 3:
                eng.submit(Request(prompt=list(range(5, 16)), max_new_tokens=4))
            eng.step()
    finally:
        jax.profiler.stop_trace()
    return trace.extract(str(logdir)), eng


def test_every_tick_holds_its_phases(traced):
    tr, _ = traced
    host = tr["host"]
    ticks = [h for h in host if h[0] == "engine.tick"]
    assert len(ticks) == 6
    for tick in ticks:
        names = {h[0] for h in host if h is not tick and _within(h, tick)}
        assert {"engine.decode", "engine.sample", "engine.commit", "engine.record"} <= names, names


def test_waits_lie_inside_phases(traced):
    tr, _ = traced
    host = tr["host"]
    phases = [h for h in host if h[0] in PHASES]
    waits = [h for h in host if h[0] == "engine.wait"]
    assert waits
    for w in waits:
        assert any(_within(w, p) for p in phases), w


def test_admission_ticks_hold_prefill(traced):
    tr, eng = traced
    host = tr["host"]
    ticks = sorted((h for h in host if h[0] == "engine.tick"), key=lambda h: h[1])
    logged = eng.metrics_log[-len(ticks):]
    admitting = [t for t, m in zip(ticks, logged) if m["prefill_tokens"]]
    assert admitting
    for tick in admitting:
        assert any(h[0] == "engine.prefill" and _within(h, tick) for h in host)


def test_default_obs_writes_no_chrome_events(traced):
    _, eng = traced
    assert not eng.obs.tracer.enabled and eng.obs.tracer.n_events == 0


def test_enabled_tracer_writes_phases_on_the_engine_track(grouped):
    cfg, params = grouped
    obs = Obs(trace=True)
    eng = _engine(cfg, params, obs=obs)
    eng.submit(Request(prompt=list(range(1, 12)), max_new_tokens=3))
    ticks = 0
    while eng.step():
        ticks += 1
    begun = [e["name"] for e in obs.tracer.trace_events(close_open=False)
             if e["ph"] == "B" and e.get("cat") == "engine"]
    assert begun.count("engine.tick") == ticks + 1 and ticks == len(eng.metrics_log)
    assert {"engine.prefill", "engine.decode", "engine.wait"} <= set(begun)


def _reference_routed(cfg, params, args) -> list:
    """Per MoE layer in layer order: experts with a nonzero bincount of the
    gating's primary choices (top-1 here, so every choice), from the
    routing-stats path of the same decode step."""
    _, _, stats = paged_ragged_decode_step(cfg, params, *args, return_routing=True)
    out = []
    for i, seg in enumerate(cfg.segments):
        moe = [f"pos{j}" for j, ls in enumerate(seg.pattern) if ls.ffn.kind == "moe"]
        f = np.stack([np.asarray(stats[f"seg{i}"][pk].f) for pk in moe], axis=1)
        out += (f > 0).sum(-1).reshape(-1).tolist()
    return out


@pytest.mark.parametrize("impl", ["grouped", "einsum"])
def test_experts_routed_matches_the_gating(impl):
    cfg = _cfg(impl)
    params = init_params(cfg, jax.random.PRNGKey(1))
    eng = _engine(cfg, params)
    want = []
    dec = eng._decode

    def decode(params, tokens, positions, active, caches, tables):
        # the reference runs on a copy: the step donates its caches
        want.append(_reference_routed(cfg, eng.params, (tokens, positions, active,
                                                        jax.tree.map(jnp.copy, caches), tables)))
        return dec(params, tokens, positions, active, caches, tables)

    eng._decode = decode
    for k in range(3):
        eng.submit(Request(prompt=list(range(1 + k, 14 + 3 * k)), max_new_tokens=4 + k))
    eng.run_until_done()
    got = [m["experts_routed"] for m in eng.metrics_log if "experts_routed" in m]
    assert len(got) == len(want) > 0
    n_moe = sum(ls.ffn.kind == "moe" for ls in cfg.layer_specs())
    assert all(len(g) == n_moe for g in got)
    assert got == want
    # at least one tick routed rows to more than one expert of a layer
    assert max(max(g) for g in got) > 1


def test_queue_wait_and_ttft_run_from_the_due_time(grouped):
    cfg, params = grouped
    obs = Obs()
    eng = _engine(cfg, params, obs=obs)
    due = time.perf_counter() - 0.05  # submitted 50 ms after it was due
    eng.submit(Request(prompt=list(range(1, 12)), max_new_tokens=2, arrival_s=due))
    eng.run_until_done()
    wait = obs.metrics.histogram("serve.queue_wait_s")
    ttft = obs.metrics.histogram("serve.ttft_s")
    assert wait.count == 1 and ttft.count == 1
    # admission is immediate (a free slot), so the wait is the 50 ms late
    # submission; the log buckets are ~4% wide
    assert 0.045 <= wait.max_seen <= 0.5
    assert ttft.max_seen >= wait.max_seen
    # without a due time the timers start at submit()
    obs2 = Obs()
    eng2 = _engine(cfg, params, obs=obs2)
    eng2.submit(Request(prompt=list(range(1, 12)), max_new_tokens=2))
    eng2.run_until_done()
    assert obs2.metrics.histogram("serve.queue_wait_s").max_seen < 0.045
