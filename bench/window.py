"""The measured window: offer the traffic to the engine on the host clock,
tick the engine, and note when each output token came back.

Requests are submitted between ``engine.step()`` calls as they fall due;
each request's times run from when it was due, not from when the harness
got round to submitting it, so a stalled tick delays every later request
in the numbers too.  How late the harness submitted is recorded on its own
(``lateness_s``).  A token is stamped with the host time at which the tick
that produced it returned (the engine fences each tick on the device).
Arrivals due before the window (a mix's ramp) are offered the same way
before it opens; their tokens before the window count for nothing.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field


class Drained(RuntimeError):
    """A backlog ran out of waiting requests inside the window: the window
    no longer measured a saturated engine."""


@dataclass
class Record:
    t0: float = 0.0  # window start (host perf_counter)
    t1: float = 0.0  # window end: the return of the last tick
    due: dict = field(default_factory=dict)  # rid -> due time
    arrival: dict = field(default_factory=dict)  # rid -> index into the arrivals
    tokens: dict = field(default_factory=dict)  # rid -> [host time of each token], ramp included
    lateness_s: list = field(default_factory=list)
    ticks: int = 0  # ticks inside the window
    log_at_open: int = 0  # length of the engine's tick log when the window opened
    failed: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _observe(engine, rec: Record, now: float, done_seen: set) -> None:
    """Stamp every token that appeared since the last look with ``now``."""
    counts = {}
    for s in engine.slots:
        if s.active and s.request_id in rec.due:
            counts[s.request_id] = len(s.generated)
    for rid in engine.done.keys() - done_seen:
        done_seen.add(rid)
        if rid in rec.due:
            counts[rid] = len(engine.done[rid].tokens)
    for rid, n in counts.items():
        got = rec.tokens.setdefault(rid, [])
        got.extend([now] * (n - len(got)))


def run(engine, arrivals, seconds: float, make_request, *, ramp_s: float = 0.0,
        drains_fail: bool = False, hooks=None) -> Record:
    """Drive the engine through ``ramp_s`` seconds of the arrivals due
    before the window, then for ``seconds`` of host time.  ``hooks``
    (traced runs only) is called before each tick with the seconds since
    the window opened (negative in the ramp) and may start the profiler; it
    never sees the untraced runs."""
    rec = Record()
    done_seen = set(engine.done)
    rec.t0 = t0 = time.perf_counter() + ramp_s
    opened = False
    t_end = t0 + seconds
    due = [t0 + a.due_s for a in arrivals]
    nxt = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        while nxt < len(arrivals) and due[nxt] <= now:
            a = arrivals[nxt]
            try:
                rid = engine.submit(make_request(a.prompt, a.max_new_tokens))
            except ValueError:
                rec.failed += 1
            else:
                rec.due[rid], rec.arrival[rid] = due[nxt], nxt
            rec.lateness_s.append(time.perf_counter() - due[nxt])
            nxt += 1
        if hooks is not None:
            hooks(now - t0)
        if not opened and now >= t0:
            opened = True
            rec.log_at_open = len(getattr(engine, "metrics_log", ()))
        if engine.queue or any(s.active for s in engine.slots):
            engine.step()
            rec.ticks += now >= t0
            _observe(engine, rec, time.perf_counter(), done_seen)
            if drains_fail and nxt >= len(arrivals) and not engine.queue:
                raise Drained(f"the backlog drained {time.perf_counter() - t0:.2f} s into "
                              f"a {seconds} s window")
        elif nxt < len(arrivals):
            time.sleep(max(0.0, min(due[nxt], t_end) - time.perf_counter()))
        else:
            time.sleep(max(0.0, t_end - time.perf_counter()))
    rec.t1 = time.perf_counter()
    return rec
