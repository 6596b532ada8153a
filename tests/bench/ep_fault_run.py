"""Child process of test_bench.py's expert-parallel test: the tiny
configuration on a (4,) EP mesh of four host CPU devices serves a fixed set
of requests to completion twice — as it is, and with the cross-chip
exchange of the expert outputs left out (``psum`` made the identity, so
each chip keeps only its own experts' rows) — and the plain reference
judges both.  Prints one JSON line with each run's compared numbers."""
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def numbers(conf: dict, seed: int) -> dict:
    from bench import correct, program

    cfg = program.model_config(conf)
    mesh, rules = program.serving_mesh(cfg)
    engine = program.build_engine(cfg, program.make_params(cfg, seed, mesh, rules), conf["serving"])
    kept = correct.ServedLogits(engine)
    rng = np.random.default_rng(seed)
    reqs = [rng.integers(0, conf["vocab_size"], size=n).astype(np.int32) for n in (23, 70, 41, 96)]
    ids = [engine.submit(program.request(p, 16)) for p in reqs]
    done = engine.run_until_done()
    samples = [(p, np.asarray(done[i].tokens, np.int32), 16, kept.of(i, len(done[i].tokens)))
               for p, i in zip(reqs, ids)]
    return correct.errors(conf, seed, samples)[0]


def main() -> int:
    import jax

    conf, seed = json.loads(Path(sys.argv[1]).read_text()), int(sys.argv[2])
    sound = numbers(conf, seed)
    jax.clear_caches()
    psum = jax.lax.psum
    jax.lax.psum = lambda x, axes, **kw: x
    try:
        broken = numbers(conf, seed)
    finally:
        jax.lax.psum = psum
    print(json.dumps({"sound": sound, "no_exchange": broken}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
