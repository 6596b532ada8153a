#!/usr/bin/env python3
"""Knee sweep of an open-loop mix on a configuration: the same engine
offered the mix at several fixed rates to find the highest rate it
sustains with no backlog growing.  A cell's mix file then fixes its rate at
about four fifths of that.  Run once on the chip before an open-loop cell
is added, and again by a later benchmark PR that needs to re-find a knee
an optimisation has moved.

    python3 bench/sweep.py --config <config> --traffic <mix> --seed N --ramp R --seconds S --rates 0.5 0.8 ...

Each rate starts on a drained engine, offers the mix for ``--ramp``
seconds (long enough for the requests in the system to reach their steady
number: more than a request's life) and then watches a window of
``--seconds`` (longer than a request's life).  For each rate it prints one
JSON line: requests due in the window and finished in it (per second
beside the rate), and the requests in the system (due, not finished) and
waiting (due, no first token) averaged over the first and the last third
of the window.  Below the knee both stay level and requests finish as fast
as they arrive; above it the counts climb from the first third to the last.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="a file of bench/configs/, by name")
    ap.add_argument("--traffic", required=True, help="a file of bench/mixes/, by name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--ramp", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    from bench import catalog, e2e, program, traffic, window
    from bench.run import enable_compile_cache, warm

    enable_compile_cache(ROOT)
    conf = json.loads((catalog.BENCH_DIR / "configs" / f"{args.config}.json").read_text())
    base = catalog.load_mix(args.traffic)
    cfg = program.model_config(conf)
    mesh, rules = program.serving_mesh(cfg)
    params = program.make_params(cfg, args.seed, mesh, rules)
    engine = program.build_engine(cfg, params, conf["serving"])
    warm(engine, conf["serving"], conf["vocab_size"])
    for rate in args.rates:
        mix = dict(base, arrivals=dict(base["arrivals"], rate=rate, ramp_s=args.ramp))
        arrivals = traffic.generate(mix, args.seed, args.seconds, conf["vocab_size"])
        ticks = []  # seconds since the window opened, before each tick
        rec = window.run(engine, arrivals, args.seconds, program.request, ramp_s=args.ramp,
                         hooks=ticks.append)
        t0, span = rec.t0, rec.seconds
        ticks = [t0 + t for t in ticks if t >= 0]
        first = {rid: ts[0] for rid, ts in rec.tokens.items() if ts}
        end = {rid: rec.tokens[rid][-1] for rid in rec.due if rid in engine.done}

        def count(t, since):
            return sum(1 for rid, d in rec.due.items() if d <= t and since.get(rid, 1e300) > t)

        def thirds(since):
            parts = [[count(t, since) for t in ticks if lo <= (t - t0) / span < hi]
                     for lo, hi in ((0, 1 / 3), (2 / 3, 1.01))]
            return [sum(p) / max(1, len(p)) for p in parts]

        due_in = sum(t0 <= d < rec.t1 for d in rec.due.values())
        fin_in = sum(t0 <= e < rec.t1 for e in end.values())
        print(json.dumps({
            "rate": rate, "ramp_s": args.ramp, "window_s": span,
            "due_in_window": due_in, "finished_in_window": fin_in,
            "finished_per_s": fin_in / span,
            "in_system_thirds": thirds(end), "waiting_thirds": thirds(first),
            "in_system_at_close": count(rec.t1, end), "waiting_at_close": count(rec.t1, first),
            "output_tok_s": e2e.output_tok_s(rec), "ttft_p95_ms": e2e.ttft_p95_ms(rec),
            "tpot_ms": e2e.tpot_ms(rec)}), flush=True)
        t = time.perf_counter()
        engine.queue.clear()
        engine.run_until_done()
        print(f"drained in {time.perf_counter() - t:.1f} s", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
