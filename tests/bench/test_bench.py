"""CPU tests of the chip benchmark in ``bench/``: the generator, the
end-to-end arithmetic, the trace reduction on a small recorded trace, the
FLOP and byte functions, the plain reference against the served engine,
the harness end to end on a tiny cell, and that a run whose timed path is
broken comes out not correct.

The tiny cell is a reduced PR-MoE (6 layers: a 4-expert MoE layer, then
two 8-expert ones, each with the residual MLP) built in a scratch copy of
the benchmark's data files, which is also how a new configuration, mix or
metric file is shown to be picked up with no edit.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import catalog, e2e, flops, trace, traffic, window  # noqa: E402
from bench.peaks import PEAKS, peaks  # noqa: E402

V5E = PEAKS["TPU v5 lite"]
TINY = "tiny-prmoe-4-8"
CELL = "tiny.chat"

TINY_CONF = {
    "name": TINY, "source": "https://arxiv.org/abs/2201.05596",
    "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 16, "intermediate_size": 256,
    "vocab_size": 512, "max_seq_len": 2048, "num_hidden_layers": 6, "act": "gelu", "top_k": 1,
    "rms_eps": 1e-6, "rope_theta": 10000.0, "tie_embeddings": True, "param_dtype": "bfloat16",
    "segments": [
        {"pattern": [{"ffn": "dense"}, {"ffn": "moe", "experts": 4, "residual": True}], "repeats": 1},
        {"pattern": [{"ffn": "dense"}, {"ffn": "moe", "experts": 8, "residual": True}], "repeats": 2}],
    "builder": {"fn": "repro.core.prmoe:nlg_moe", "args": [TINY, 6, 64, 4, [4, 8]],
                "kwargs": {"residual": True, "vocab": 512}},
    "serving": {"chips": 1, "ep_mesh": [], "moe_impl": "grouped", "slots": 4, "capacity": 128,
                "page_size": 16, "prefill_chunk": 32, "n_pages": 24},
}
# the 90th percentile of the served token's logit error against the
# reference: sound CPU runs of the tiny cell read 0.0024-0.0038 on eight
# seeds, the fp8 control 0.029-0.041, the planted faults 0.040 or more
TINY_LIMITS = {"served_logit_err_p90": 0.012}
TINY_MIX = {"name": "tiny_chat", "arrivals": {"kind": "poisson", "rate": 40.0},
            "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5, "min": 16, "max": 80},
            "output": {"dist": "uniform", "min": 8, "max": 16}}


def make_tiny_root(root: Path) -> Path:
    """A checkout holding the benchmark's files plus a tiny configuration,
    mix, cell and limit added as files and entries only."""
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / f"{TINY}.json").write_text(json.dumps(TINY_CONF))
    (root / "bench" / "mixes" / "tiny_chat.json").write_text(json.dumps(TINY_MIX))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(json.dumps(
        {name: {"limit": v} for name, v in TINY_LIMITS.items()}))
    bench["configs"].append({"name": TINY, "source": TINY_CONF["source"],
                             "file": f"bench/configs/{TINY}.json", "reduced": ["num_hidden_layers"],
                             "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": TINY, "traffic": "tiny_chat", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))


# -- traffic ----------------------------------------------------------------

@pytest.mark.parametrize("mix", ["decode_backlog", "long_prompt"])
def test_generator_deterministic_per_seed(mix):
    m = catalog.load_mix(mix)
    a = traffic.generate(m, 2**33 + 7, 20, 51200)
    b = traffic.generate(m, 2**33 + 7, 20, 51200)
    c = traffic.generate(m, 7, 20, 51200)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed: the same sizes at the same times, other tokens
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert [(x.due_s, x.max_new_tokens) for x in a] == [(x.due_s, x.max_new_tokens) for x in c]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    for x in a:  # within the mix's clipping, and inside the context
        assert m["prompt"]["min"] <= len(x.prompt) <= m["prompt"]["max"]
        assert len(x.prompt) + x.max_new_tokens <= 2048


class FakeSlot:
    def __init__(self):
        self.active, self.request_id, self.generated, self.prefilling = False, -1, [], False


class FakeEngine:
    """Admits one request per step into one slot and emits one token per
    step, each step taking ``tick_s`` of host time."""

    def __init__(self, tick_s=0.01):
        self.slots, self.queue, self.done, self.tick_s, self.n = [FakeSlot()], [], {}, tick_s, 0

    def submit(self, req):
        self.queue.append((self.n, req))
        self.n += 1
        return self.n - 1

    def step(self):
        import time

        time.sleep(self.tick_s)
        s = self.slots[0]
        if not s.active and self.queue:
            rid, req = self.queue.pop(0)
            s.active, s.request_id, s.generated, s.budget = True, rid, [], req.max_new_tokens
        if s.active:
            s.generated.append(1)
            if len(s.generated) == s.budget:
                from types import SimpleNamespace

                self.done[s.request_id] = SimpleNamespace(tokens=list(s.generated))
                s.active = False


def _req(prompt, n):
    from types import SimpleNamespace

    return SimpleNamespace(prompt=prompt, max_new_tokens=n)


def test_open_loop_times_run_from_due_time():
    arr = [traffic.Arrival(0.0, np.zeros(4, np.int32), 5), traffic.Arrival(0.01, np.zeros(4, np.int32), 5)]
    rec = window.run(FakeEngine(), arr, 0.3, _req)
    # the second request waited behind the first's five ticks: its first
    # token is late by about that much, counted from when it was due
    t = e2e.ttft_s(rec)
    assert len(t) == 2 and t[1] > 0.04
    assert rec.due[1] - rec.due[0] == pytest.approx(0.01)
    assert e2e.output_tok_s(rec) == pytest.approx(10 / rec.seconds)


def test_ramp_tokens_count_for_nothing():
    """Arrivals due before the window are served in the ramp; only tokens
    inside the window count, and a request whose first token came in the
    ramp has no TTFT in the window."""
    arr = [traffic.Arrival(-0.2, np.zeros(4, np.int32), 30), traffic.Arrival(0.05, np.zeros(4, np.int32), 3)]
    rec = window.run(FakeEngine(), arr, 0.3, _req, ramp_s=0.2)
    assert rec.tokens[0][0] < rec.t0 <= rec.tokens[0][-1]
    assert len(e2e.ttft_s(rec)) == 1  # the second request's
    n_in = sum(t >= rec.t0 for ts in rec.tokens.values() for t in ts)
    assert e2e.output_tok_s(rec) == pytest.approx(n_in / rec.seconds)


def test_backlog_drain_fails_the_run():
    arr = [traffic.Arrival(0.0, np.zeros(4, np.int32), 2) for _ in range(3)]
    with pytest.raises(window.Drained):
        window.run(FakeEngine(0.005), arr, 1.0, _req, drains_fail=True)


def test_e2e_arithmetic():
    rec = window.Record(t0=0.0, t1=2.0, due={0: 0.0, 1: 0.5},
                        tokens={0: [0.1, 0.2, 0.4], 1: [0.9, 1.0]})
    assert e2e.output_tok_s(rec) == pytest.approx(2.5)
    assert e2e.tpot_ms(rec) == pytest.approx(1e3 * (0.3 + 0.1) / 3)
    assert e2e.ttft_s(rec) == pytest.approx([0.1, 0.4])
    assert e2e.itl_p95_ms(rec) == pytest.approx(1e3 * np.percentile([0.1, 0.2, 0.1], 95))


# -- trace reduction ----------------------------------------------------------

def test_interval_arithmetic():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert trace.merged([(20, 30), (0, 10), (5, 15), (30, 31)]) == [[0, 15], [20, 31]]


def test_reduction_on_recorded_trace():
    """The small trace kept under bench/testdata: a few ticks of a tiny
    model on one v5e, with the harness's call log of the same ticks."""
    rec = json.loads((ROOT / "bench" / "testdata" / "calls_small.json").read_text())
    tr = trace.load(ROOT / "bench" / "testdata" / "trace_small.json.gz")
    from types import SimpleNamespace

    from bench import layers

    conf = json.loads((ROOT / rec["config_file"]).read_text())
    ctx = SimpleNamespace(conf=conf, peak=V5E, chips=rec["chips"], trace=tr, calls=rec["calls"],
                          trace_t0=rec["trace_t0"], trace_t1=rec["trace_t1"], notes=[])
    for program in ("decode", "prefill"):
        runs = layers.per_device_runs(ctx, program)
        assert all(len(r) == len(layers.traced_calls(ctx, program)) for _, r in runs)
        assert layers.program_ms(ctx, program) > 0
        mfu = layers.step_mfu(ctx, program)
        assert 0 < mfu <= 105
    for program, kernel in (("decode", "paged_decode_attn"), ("prefill", "prefill_attn")):
        share = layers.kernel_roofline(ctx, program, kernel)
        assert share is not None and 0 < share <= 105
    assert layers.kernel_ms_per_run(ctx, "decode", "grouped_mlp") > 0
    busy = trace.busy_ns(next(iter(tr["devices"].values())))
    assert 0 < busy <= (rec["trace_t1"] - rec["trace_t0"]) * 1e9 * 1.05
    assert trace.top_ops(tr) and trace.idle_gaps(tr)


# -- FLOP and byte functions ----------------------------------------------------

def test_flops_match_hand_counts():
    conf = json.loads((ROOT / "bench" / "configs" / "nlg-350m-prmoe-32-64.json").read_text())
    d, f, V = 1024, 4096, 51200
    attn = 4 * d * d
    dense = 2 * d * f
    # 12 dense layers, 10 MoE layers of 32 experts, 2 of 64; each MoE layer
    # multiplies through its router, one expert and the residual MLP
    want = 24 * attn + 12 * dense + 10 * (d * 32 + 2 * dense) + 2 * (d * 64 + 2 * dense)
    assert flops.matmul_params_per_token(conf) == want
    w = flops.decode_step(conf, [10, 30])
    assert w["attn_flops"] == 4 * 16 * 64 * 40 * 24
    assert w["attn_bytes"] == 24 * (40 * 2 * 16 * 64 * 2 + 2 * 2 * 16 * 64 * 2)
    assert w["model_flops"] == 2 * (2 * want + 2 * V * d) + w["attn_flops"]
    p = flops.prefill_call(conf, [(0, 4, True), (16, 2, False)])
    assert p["attn_flops"] == 4 * 16 * 64 * 24 * ((1 + 2 + 3 + 4) + (17 + 18))
    assert p["model_flops"] == 6 * 2 * want + p["attn_flops"] + 2 * V * d
    share, bound = flops.roofline(197e12, 1.0, 2.0, V5E)
    assert share == pytest.approx(50.0) and bound == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


# -- the harness finds files by name ---------------------------------------------

def test_new_files_are_picked_up_without_edits(tiny_root):
    c = catalog.cell(CELL, tiny_root)
    assert c["config"]["name"] == TINY and c["mix"]["name"] == "tiny_chat"
    names = {m["name"] for m in c["per_layer"]}
    assert {"decode_mfu", "paged_decode_attn_roofline"} <= names
    metric = tiny_root / "bench" / "metrics" / "ticks_seen.py"
    metric.write_text("def read(ctx):\n    return float(len(ctx.ticks))\n")
    from types import SimpleNamespace

    assert catalog.metric_reader("ticks_seen", tiny_root)(SimpleNamespace(ticks=[1, 2])) == 2.0
    for m in catalog.load_benchmark()["per_layer"]:
        assert callable(catalog.metric_reader(m["name"]))


# -- the reference against the served engine, and the harness end to end -------

def _serve_tiny(seed, n=3):
    from bench import program

    cfg = program.model_config(TINY_CONF)
    params = program.make_params(cfg, seed)
    eng = program.build_engine(cfg, params, TINY_CONF["serving"])
    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, 512, size=int(L)).astype(np.int32), 8) for L in (37, 70, 20)[:n]]
    ids = [eng.submit(program.request(p, k)) for p, k in reqs]
    done = eng.run_until_done()
    return [(p, np.asarray(done[i].tokens, np.int32)) for (p, _), i in zip(reqs, ids)]


def test_reference_agrees_with_served_engine():
    """Prefill into pages, paged decode and grouped MoE dispatch over two
    expert counts with the residual branch, against the float32 reference
    drawn from the same seed: every served token is the reference's best or
    within bf16 rounding of it, and the fp8 control departs further."""
    from bench import reference

    seed = 2**32 + 11
    samples = _serve_tiny(seed)
    ref, ctrl = reference.served_logits(TINY_CONF, seed, samples, rows=4, length=128, control=True)
    gaps = np.concatenate(ref["best"]) - np.concatenate(ref["at"])
    assert gaps.max() < 0.05, gaps.max()
    assert np.mean(gaps == 0) > 0.8
    assert np.max(np.concatenate(ref["best"]) - np.concatenate(ctrl["at"])) > gaps.max()


@pytest.fixture
def own_cache():
    """The harness turns JAX's persistent compilation cache on for its
    process; put the settings back so no later test in this worker writes
    there."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_control_reads_above_program(tiny_root, own_cache):
    """The limit-setting readings run on the tiny cell, both sides through
    the run's own judge: the program within the tiny limit.  At d_model 64
    the fp8 control does not always read above the served path (it does at
    the cells' widths on the chip, PERF.md); that is not asserted here."""
    from bench import control

    r = control.readings(CELL, 2**31 + 9, 1.5, tiny_root)
    assert r["requests"] > 0 and r["tokens"] > 0 and r["short"] == 0
    assert r["limit"] == TINY_LIMITS
    for side in ("program", "control"):
        assert all(np.isfinite(v) and v > 0 for v in r[side].values()), r
        assert r[f"{side}_correct"] is all(r[side][n] <= TINY_LIMITS[n] for n in TINY_LIMITS)


def _run_tiny(tiny_root, capsys, *extra, hook=None):
    from bench import run

    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "3", *extra],
                  root=tiny_root, require_tpu=False, engine_hook=hook, peak=V5E)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]) if out else None


def test_harness_end_to_end(tiny_root, capsys, own_cache):
    rc, res = _run_tiny(tiny_root, capsys)
    assert rc == 0, res
    assert set(res["metrics"]) == {"output_tok_s", "tpot_ms", "itl_p95_ms", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    checks = res["checks"]
    assert list(res)[-1] == "checks" and set(checks) == {"short_answers", *TINY_LIMITS}
    assert all(c["value"] is not None for c in checks.values())
    assert res["correct"] is all(c["value"] <= c["limit"] for c in checks.values())
    assert res["device"]["platform"] == "cpu"


def test_harness_traced_run(tiny_root, capsys, own_cache, monkeypatch):
    """A traced run reports the cell's per-layer metrics that its host
    readers find (the CPU has no device trace), and the traced window."""
    from bench import run

    monkeypatch.setattr(run, "TRACE_SECONDS", 1.0)
    rc, res = _run_tiny(tiny_root, capsys, "--trace", "1")
    assert rc == 0, res
    assert "decode_rows_per_tick" in res["metrics"]
    assert 0.5 < res["device"]["window_s"] < 3 and "breakdown" in res
    assert list(res)[-1] == "checks"


def test_harness_refuses_without_tpu(tiny_root, capsys):
    from bench import run

    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"], root=tiny_root)
    assert rc != 0 and capsys.readouterr().out == ""


def _fault_state_unchanged(engine):
    dec = engine._decode

    def call(params, tokens, positions, active, caches, tables):
        import jax
        import jax.numpy as jnp

        kept = jax.tree.map(jnp.copy, caches)
        logits, _, routing = dec(params, tokens, positions, active, caches, tables)
        return logits, kept, routing

    engine._decode = call


def _fault_half_batch(engine):
    import jax.numpy as jnp

    dec = engine._decode

    def call(params, tokens, positions, active, caches, tables):
        half = jnp.arange(active.shape[0]) >= active.shape[0] // 2
        tables = jnp.where(half[:, None], -1, tables)
        return dec(params, tokens, positions, active, caches, tables)

    engine._decode = call


def _fault_token_altered(engine):
    dec = engine._decode

    def call(*a):
        logits, caches, routing = dec(*a)
        return logits.at[:, 7].add(1e4), caches, routing

    engine._decode = call


@pytest.mark.parametrize("fault", [_fault_state_unchanged, _fault_half_batch, _fault_token_altered],
                         ids=["state_unchanged", "half_batch", "token_altered"])
def test_broken_timed_path_is_not_correct(tiny_root, capsys, own_cache, fault):
    rc, res = _run_tiny(tiny_root, capsys, hook=fault)
    assert rc == 0 and res["correct"] is False, res["checks"]


def test_exchange_left_out_is_not_correct(tmp_path):
    """The tiny configuration on a (4,) EP mesh of host CPU devices, in a
    child process, serving a fixed set of requests: within the tiny cell's
    limit as it is, past it with the expert-output exchange between chips
    left out (90th percentiles of the served logit's error: sound
    0.0024-0.0028, without the exchange 0.050-0.071 on seeds 977-979)."""
    import os
    import subprocess

    conf = dict(TINY_CONF, name=TINY + "-ep4", serving=dict(TINY_CONF["serving"], chips=4, ep_mesh=[4]))
    path = tmp_path / "tiny_ep4.json"
    path.write_text(json.dumps(conf))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(Path(__file__).parent / "ep_fault_run.py"), str(path), "977"],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(res["sound"][n] <= lim for n, lim in TINY_LIMITS.items()), res
    assert any(res["no_exchange"][n] > lim for n, lim in TINY_LIMITS.items()), res
