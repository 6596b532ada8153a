"""Whether what the timed path served is right.

During the window, ``ServedLogits`` keeps the logit of every token the
engine serves: each decode or batched-prefill call's best logit per row
(the served token's, as every served token is greedy), reduced on the
device and read back only after the window.  Once the window has closed, a
sample of the requests it finished — the one with the most served tokens
always among them, the rest drawn from the seed — is run through the plain
float32 reference, teacher-forced on each prompt and its served tokens.

The number compared is the 90th percentile, over every served position of
the sample, of the error of the served token's logit against the
reference's logit of that token.  The fp8 control, in the program's place,
is judged by the same error of the token it puts first.

Why not the gap of a served token below the reference's best: with random
weights most served positions repeat a token by a margin of about two
logits, so neither bfloat16 nor fp8 changes the token there, and a run's
tokens separate the program from the control on only the few positions
near a tie (on one seed both read 0).  The logit itself departs at every
position.  Why not the widest error: under top-1 routing a router near-tie
sends a token to another expert at bfloat16 than at float32, and that one
position's logit moves about as far as the control's do; such positions
are a few in a hundred, under the 90th percentile.  The widest gap and the
widest error are logged beside the number.

A sampled request that came back with fewer tokens than it asked for
fails the run on its own.  The limits of each cell are in
``limits/<workload>.json``, with the readings they were set from: the
program's largest over the seeds that ``bench/control.py`` read (the lower
reading) and the fp8 control's smallest (the upper); the file says how
many seeds.
"""
from __future__ import annotations

import numpy as np

SAMPLE = 16  # requests compared per run (all finished ones if fewer)
NUMBERS = ("served_logit_err_p90",)


class ServedLogits:
    """Wraps the engine's decode and batched-prefill entries: after each
    call, the best logit of every row, reduced on the device, and on the
    host which (request, token index) each row served.  The reductions are
    read back only by ``of``, after the window."""

    def __init__(self, engine):
        import jax
        import jax.numpy as jnp

        self.calls = []  # each call's best logit per row, on the device
        self._at = {}  # (rid, k) -> (call index, row)
        self._host = []
        best = jax.jit(lambda lg: jnp.max(lg, axis=-1).astype(jnp.float32))
        dec, pre = engine._decode, engine._prefill_chunk_batched

        def note(logits, rows):
            self.calls.append(best(logits))
            for i, key in rows.items():
                self._at[key] = (len(self.calls) - 1, i)

        def decode(params, tokens, positions, decoding, *rest):
            out = dec(params, tokens, positions, decoding, *rest)
            rows = {int(i): (engine.slots[i].request_id, len(engine.slots[i].generated))
                    for i in np.flatnonzero(np.asarray(decoding))}
            note(out[0], rows)
            return out

        def prefill(params, tokens, positions, reset, active, *rest):
            out = pre(params, tokens, positions, reset, active, *rest)
            pos, rows = np.asarray(positions), {}
            for i in np.flatnonzero(np.asarray(active)):
                s = engine.slots[i]
                if pos[i].max() + 1 == len(s.prefill_ctx):  # this chunk ends the prompt
                    rows[int(i)] = (s.request_id, len(s.generated))
            note(out[0], rows)
            return out

        engine._decode, engine._prefill_chunk_batched = decode, prefill

    def of(self, rid, n: int) -> np.ndarray:
        """The served logits of a request's first ``n`` tokens (NaN where
        none was kept)."""
        if len(self._host) < len(self.calls):
            self._host = [np.asarray(x) for x in self.calls]
        out = np.full(n, np.nan, np.float32)
        for k in range(n):
            if (rid, k) in self._at:
                c, i = self._at[(rid, k)]
                out[k] = self._host[c][i]
        return out


def sample(rec, done: dict, arrivals, seed: int, kept: ServedLogits) -> list:
    """``[(prompt, served, asked, served logits)]`` of the sampled finished
    requests."""
    fin = sorted(rid for rid in rec.due if rid in done)
    if not fin:
        return []
    longest = max(fin, key=lambda r: (len(done[r].tokens), -r))
    rest = [r for r in fin if r != longest]
    rng = np.random.default_rng([seed, 1])
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[: SAMPLE - 1]]
    out = []
    for rid in pick:
        a = arrivals[rec.arrival[rid]]
        served = np.asarray(done[rid].tokens, np.int32)
        out.append((np.asarray(a.prompt, np.int32), served, a.max_new_tokens,
                    kept.of(rid, len(served))))
    return out


def errors(conf: dict, seed: int, samples: list, control: bool = False) -> tuple:
    """(each compared number's value, numbers logged beside them: the
    widest error, and the widest gap of a served token below the
    reference's best logit) over the sample's served positions; with
    ``control``, of the fp8 control in the program's place."""
    from bench import reference

    ref = reference.served_logits(conf, seed, [(p, s) for p, s, _, _ in samples],
                                  rows=SAMPLE, length=conf["serving"]["capacity"],
                                  control=control)
    if control:
        ref, ctrl = ref
        err = np.abs(np.concatenate(ctrl["own"]) - np.concatenate(ctrl["at"]))
    else:
        err = np.abs(np.concatenate([lg for _, _, _, lg in samples]) - np.concatenate(ref["at"]))
    logged = {"served_logit_err_max": float(err.max()),
              "widest_gap": float(np.max(np.concatenate(ref["best"]) - np.concatenate(ref["at"])))}
    if not np.all(np.isfinite(err)):  # a logit lost or not a number fails the run
        return {"served_logit_err_p90": float("inf")}, logged
    return {"served_logit_err_p90": float(np.quantile(err, 0.9))}, logged


def judge(conf: dict, seed: int, samples: list, limits: dict, short: int,
          control: bool = False) -> tuple:
    """(correct, checks, tokens compared, logged): checks maps each
    compared number's short name to its value and its limit (at most);
    logged holds the numbers ``errors`` logs beside them.  ``short`` counts
    the window's finished requests that came back with fewer tokens than
    they asked for."""
    values, logged = errors(conf, seed, samples, control) if samples else ({}, {})
    checks = {"short_answers": {"value": short, "limit": 0}}
    for name in NUMBERS:
        checks[name] = {"value": values.get(name), "limit": limits[name]["limit"]}
    # a cell whose limit is not set yet, or a run with nothing to compare, is never correct
    ok = short == 0 and all(c["value"] is not None and c["limit"] is not None
                            and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks, sum(len(s) for _, s, _, _ in samples), logged
