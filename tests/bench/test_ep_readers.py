"""CPU tests of the readers of the expert-parallel exchange
(bench/collectives.py and their files under bench/metrics/): on a
hand-built extract of four chips whose answers are known to the
nanosecond (collectives overlapped by compute, collectives alone, a
batched prefill tick), with a tick log that carries ``ep_rows``; and on the
one-chip trace recorded on a v5e (bench/testdata), which has neither
collectives nor the counter, so every reader returns nothing there."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import catalog, trace  # noqa: E402

STEP, PREFILL = "jit__step(1234)", "jit__prefill_chunk_batched_fn(77)"


def _chip(with_gather: bool = True) -> dict:
    """Two decode runs and a prefill run.  Decode run 1: ``psum.8`` half
    under a fusion (100 of its 200 ns exposed), then ``all-gather.3`` alone
    (100 ns); run 2: an async all-reduce whose start and done overlap (a
    union of 200 ns), its last 50 ns under a fusion.  Prefill: ``psum.9``
    alone (500 ns) and ``all-to-all.1`` wholly under a fusion (200 ns).  A
    gather outside every program run counts for nothing."""
    ops = [["fusion.1", 0, 400], ["psum.8", 300, 500], ["all-gather.3", 600, 700],
           ["fusion.2", 700, 1000],
           ["all-reduce-start.1", 2100, 2200], ["all-reduce-done.1", 2150, 2300],
           ["fusion.3", 2250, 2600],
           ["psum.9", 4100, 4600], ["fusion.4", 4900, 5300], ["all-to-all.1", 5000, 5200],
           ["all-gather.9", 7000, 7100]]
    if not with_gather:
        ops = [o for o in ops if o[0] != "all-gather.3"]
    return {"modules": [[STEP, 0, 1000], [STEP, 2000, 3000], [PREFILL, 4000, 6000]], "ops": ops}


def _extract() -> dict:
    chips = [_chip(), _chip(with_gather=False), _chip(), _chip()]
    return {"host": [], "devices": {str(i): c for i, c in enumerate(chips)}}


TICKS = [{"tokens_this_tick": 0, "prefill_tokens": 256},
         {"tokens_this_tick": 32, "ep_rows": [[20, 4, 4, 4], [8, 8, 8, 8]]},
         {"tokens_this_tick": 32, "ep_rows": [[16, 16, 0, 0], [32, 0, 0, 0]]},
         {"tokens_this_tick": 32, "ep_rows": [[8, 8, 8, 8], [10, 10, 6, 6]]}]

# ms per run, the mean over the four chips (chip 1 has no all-gather.3)
EXPECTED = {
    "collective_ms.decode": ((300 + 200) / 2 * 3 + 400 / 2) / 4 / 1e6,
    "collective_exposed_ms.decode": ((100 + 100 + 150) / 2 * 3 + (100 + 150) / 2) / 4 / 1e6,
    "collective_ms.prefill": 700 / 1e6,
    # the last two decode ticks (the trace holds two decode runs)
    "ep_rows_max_share.decode": (50 + 100 + 25 + 31.25) / 4,
}


def _ctx(tr, ticks, chips=4):
    return SimpleNamespace(trace=tr, ticks=ticks, chips=chips, conf={}, notes=[])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_ep_reader(name):
    read = catalog.metric_reader(name)
    assert read(_ctx(_extract(), TICKS)) == pytest.approx(EXPECTED[name])
    # nothing to read: no collectives and no counter (one chip)
    bare = _extract()
    for dev in bare["devices"].values():
        dev["ops"] = [o for o in dev["ops"] if o[0].startswith("fusion")]
    untagged = [{k: v for k, v in m.items() if k != "ep_rows"} for m in TICKS]
    assert read(_ctx(bare, untagged)) is None
    # the trace holds no run of the program at all
    empty = copy.deepcopy(_extract())
    for dev in empty["devices"].values():
        dev["modules"] = []
    assert read(_ctx(empty, TICKS)) is None
    # the one-chip cell's trace recorded on a v5e, and its tick log
    rec = json.loads((ROOT / "bench" / "testdata" / "ticks_spans_small.json").read_text())
    tr = trace.load(ROOT / "bench" / "testdata" / "trace_spans_small.json.gz")
    assert read(_ctx(tr, rec["ticks"], chips=rec["chips"])) is None
