#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

For each seed, in one process: serve the cell's traffic through the timed
path for a run's window (its ramp included), sample the finished requests
as a run does, free the engine, and judge the same sample twice with
``correct.judge`` against the cell's current limit: once as a run does
(the program's reading, the lower end of a limit), once with the fp8
control in the program's place (the upper end; the control has to come
out as not correct).  The benchmark's own runs never run the control.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--seconds S]

Prints one JSON line per seed and a last line with the largest program
reading and the smallest control reading of each compared number.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload: str, seed: int, seconds: float, root: Path = ROOT) -> dict:
    from bench import catalog, correct, program, traffic, window
    from bench.run import warm

    c = catalog.cell(workload, root)
    conf, mix = c["config"], c["mix"]
    cfg = program.model_config(conf)
    mesh, rules = program.serving_mesh(cfg)
    params = program.make_params(cfg, seed, mesh, rules)
    engine = program.build_engine(cfg, params, conf["serving"])
    kept = correct.ServedLogits(engine)
    warm(engine, conf["serving"], conf["vocab_size"])
    arrivals = traffic.generate(mix, seed, seconds, conf["vocab_size"])
    rec = window.run(engine, arrivals, seconds, program.request,
                     ramp_s=mix["arrivals"].get("ramp_s", 0.0))
    done = dict(engine.done)
    short = sum(len(done[r].tokens) != arrivals[rec.arrival[r]].max_new_tokens
                for r in rec.due if r in done)
    samples = correct.sample(rec, done, arrivals, seed, kept)
    del engine, params, kept
    gc.collect()
    limits = catalog.load_limits(workload, root)
    out = {"seed": seed, "requests": len(samples), "short": short}
    for side, control in (("program", False), ("control", True)):
        t = time.perf_counter()
        ok, checks, n_tok, logged = correct.judge(conf, seed, samples, limits, short,
                                                  control=control)
        out[side] = {name: checks[name]["value"] for name in correct.NUMBERS}
        out[f"{side}_logged"] = logged
        out[f"{side}_correct"] = bool(ok)
        out[f"{side}_reference_s"] = time.perf_counter() - t
        out["tokens"] = n_tok
    out["limit"] = {name: limits[name]["limit"] for name in correct.NUMBERS}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None, help="default: the benchmark's run_seconds")
    args = ap.parse_args()
    from bench import catalog, correct
    from bench.run import enable_compile_cache

    enable_compile_cache(ROOT)
    seconds = args.seconds or catalog.load_benchmark()["run_seconds"]
    out = []
    for seed in args.seeds:
        r = readings(args.workload, seed, seconds)
        out.append(r)
        print(json.dumps(r), flush=True)
    read = [r for r in out if r["requests"]]
    print(json.dumps({"workload": args.workload, **{
        name: {"program_max": max((r["program"][name] for r in read), default=None),
               "control_min": min((r["control"][name] for r in read), default=None)}
        for name in correct.NUMBERS}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
