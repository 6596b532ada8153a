#!/usr/bin/env python3
"""One run of one benchmark cell on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
(``bench/configs/``), a traffic mix (``bench/mixes/``) and its chips.  The
run makes the weights on the device from the seed, builds the serving
engine, warms up the two programs the window will run, offers the mix's
requests for ``--seconds`` on the host clock, and then checks a sample of
the finished requests against the plain float32 reference.  The last line
of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's last
seconds), ``device`` and, traced, ``breakdown``; its last key, ``checks``,
holds each compared number beside its limit, and so do the last lines of
stderr.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_SECONDS = 5.0  # a traced run profiles the last seconds of its window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``<checkout>/.jax_cache`` — a
    fixed path, as the path is part of each entry's key.  Every program is
    kept, however quick to compile, so that only a cell's first run in a
    checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CallLog:
    """Traced runs only: wraps the engine's decode and batched-prefill
    entries to note, at each call, the host time, each decoding row's
    context length and each prefill row's chunk."""

    def __init__(self, engine):
        import jax
        import numpy as np

        self.decode, self.prefill = [], []
        dec, pre = engine._decode, engine._prefill_chunk_batched

        def decode(*a):
            lengths = [s.pos + 1 for s in engine.slots if s.active and not s.prefilling]
            self.decode.append({"t": time.perf_counter(), "lengths": lengths})
            with jax.profiler.TraceAnnotation("bench.decode_call"):
                return dec(*a)

        def prefill(params, tokens, positions, reset, active, *rest):
            t = time.perf_counter()
            pos, act = np.asarray(positions), np.asarray(active)
            rows = []
            for i in np.flatnonzero(act):
                valid = pos[i][pos[i] >= 0]
                start, n = int(valid[0]), len(valid)
                rows.append((start, n, start + n == len(engine.slots[i].prefill_ctx)))
            self.prefill.append({"t": t, "rows": rows})
            with jax.profiler.TraceAnnotation("bench.prefill_call"):
                return pre(params, tokens, positions, reset, active, *rest)

        engine._decode, engine._prefill_chunk_batched = decode, prefill
        step = engine.step

        def annotated_step():
            with jax.profiler.TraceAnnotation("bench.tick"):
                return step()

        engine.step = annotated_step


def warm(engine, serving: dict, vocab: int) -> None:
    """One request through the engine before the window: its prompt spans
    two prefill chunks and it decodes two tokens, so the batched prefill,
    the decode step, sampling and the page reset on completion are all
    compiled (or loaded from the cache) here, and none inside the window."""
    import numpy as np

    from bench import program

    prompt = np.random.default_rng(0).integers(0, vocab, size=serving["prefill_chunk"] + 1)
    engine.submit(program.request(prompt, 3))
    engine.run_until_done()


def fail(msg: str) -> int:
    log(f"bench/run.py: {msg}; no result")
    return 2


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True, engine_hook=None,
         peak: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import json

    import jax

    from bench import catalog, correct, e2e, program, trace, traffic, window
    from bench.peaks import peaks

    if not (root / "BENCHMARK.json").exists():
        return fail(f"no BENCHMARK.json at {root}")
    try:
        c = catalog.cell(args.workload, root)
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))
    chips = c["workload"]["chips"]
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        return fail(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    peak = peak or peaks(kind)
    log(f"device: platform={devices[0].platform} kind={kind} count={len(devices)}")
    log(f"compile cache: {enable_compile_cache(root)}")
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter())
        if event == "/jax/core/compile/backend_compile_duration" else None)

    conf, mix = c["config"], c["mix"]
    serving = conf["serving"]
    cfg = program.model_config(conf)
    mesh, rules = program.serving_mesh(cfg)
    t = time.perf_counter()
    params = program.make_params(cfg, args.seed, mesh, rules)
    jax.block_until_ready(params)
    log(f"weights: {conf['name']} made on the device in {time.perf_counter() - t:.2f} s")
    engine = program.build_engine(cfg, params, serving)
    if engine_hook is not None:
        engine_hook(engine)
    kept = correct.ServedLogits(engine)
    t = time.perf_counter()
    warm(engine, serving, conf["vocab_size"])
    log(f"warm-up: {time.perf_counter() - t:.2f} s")
    arrivals = traffic.generate(mix, args.seed, args.seconds, conf["vocab_size"])
    calls = CallLog(engine) if args.trace else None
    kind_mod = traffic.arrival_kind(mix["arrivals"]["kind"])
    trace_dir = root / ".bench_traces" / args.workload
    state = {}

    def hooks(elapsed):
        if "t0" not in state and elapsed >= args.seconds - TRACE_SECONDS:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans are the harness's annotations
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            state["t0"] = time.perf_counter()

    ramp_s = mix["arrivals"].get("ramp_s", 0.0)
    try:
        rec = window.run(engine, arrivals, args.seconds, program.request,
                         ramp_s=ramp_s, drains_fail=kind_mod.drains_fail,
                         hooks=hooks if args.trace else None)
    except window.Drained as e:
        return fail(str(e))
    if args.trace:
        if "t0" not in state:
            return fail("the window ended before the profiler started")
        state["t1"] = time.perf_counter()
        jax.profiler.stop_trace()
    # set-up ends where the first arrivals are offered; a mix's ramp is
    # traffic, logged apart
    setup_s = rec.t0 - ramp_s - T_PROCESS
    in_window = sum(t >= rec.t0 for t in compiles)
    in_ramp = sum(rec.t0 - ramp_s <= t < rec.t0 for t in compiles)
    late = sorted(rec.lateness_s)
    log(f"set-up {setup_s:.2f} s, then the mix's ramp {ramp_s} s ({in_ramp} compilations)")
    log(f"window: {rec.seconds:.3f} s, {rec.ticks} ticks, {len(rec.due)} requests sent; harness "
        f"lateness median {1e3 * late[len(late) // 2] if late else 0:.3f} ms, max "
        f"{1e3 * late[-1] if late else 0:.3f} ms; {in_window} compilations inside the window")

    mem = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:chips]), default=0)
    done = dict(engine.done)
    ticks = engine.metrics_log[rec.log_at_open:]
    short = sum(len(done[r].tokens) != arrivals[rec.arrival[r]].max_new_tokens
                for r in rec.due if r in done)
    samples = correct.sample(rec, done, arrivals, args.seed, kept)
    del engine, params, kept
    gc.collect()

    metrics, notes, breakdown = {}, [], None
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(mem)}
    units = {m["name"]: m["unit"] for m in c["end_to_end"] + c["per_layer"]}
    if not args.trace:
        for m in c["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else e2e.END_TO_END[m["name"]](rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        tr = trace.extract(str(trace_dir))
        shutil.rmtree(trace_dir / "plugins", ignore_errors=True)
        trace.save(tr, trace_dir / "extract.json.gz")
        busy = [trace.busy_ns(d) / 1e9 for d in tr["devices"].values()]
        ctx = SimpleNamespace(conf=conf, peak=peak, chips=chips, trace=tr,
                              calls={"decode": calls.decode, "prefill": calls.prefill},
                              trace_t0=state["t0"], trace_t1=state["t1"],
                              busy_s=sum(busy) / len(busy) if busy else 0.0,
                              window_s=state["t1"] - state["t0"], rec=rec, ticks=ticks,
                              notes=notes)
        for m in c["per_layer"]:
            v = catalog.metric_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        breakdown = {"device_ops": trace.top_ops(tr), "idle_gaps": trace.idle_gaps(tr)}

    t = time.perf_counter()
    ok, checks, n_tok, logged = correct.judge(conf, args.seed, samples,
                                              catalog.load_limits(args.workload, root), short)
    log(f"reference: {len(samples)} finished requests, {n_tok} served tokens compared in "
        f"{time.perf_counter() - t:.2f} s; not compared: {logged}")
    result = {"correct": bool(ok), "attempted": len(rec.due) + rec.failed,
              "failed": rec.failed + short, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for line in notes:
        log(line)
    for k, v in metrics.items():
        log(f"metric {k}: {v['value']} {v['unit']}")
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
