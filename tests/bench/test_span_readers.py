"""CPU tests of the readers of the engine's host tick and of the grouped
expert kernel's roofline (bench/spans.py and their files under
bench/metrics/): on a hand-built extract whose answers are known to the
nanosecond, and on a small trace recorded on one v5e with the engine's
spans (a few ticks of a tiny configuration, kept under bench/testdata)."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import catalog, spans, trace  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402

V5E = PEAKS["TPU v5 lite"]
READERS = ("host_ms_per_tick.decode", "exposed_host_ms.decode", "grouped_mlp_decode_roofline")
CONF = {
    "d_model": 64, "intermediate_size": 256, "act": "gelu", "param_dtype": "bfloat16",
    "segments": [
        {"pattern": [{"ffn": "dense"}, {"ffn": "moe", "experts": 4}], "repeats": 1},
        {"pattern": [{"ffn": "dense"}, {"ffn": "moe", "experts": 8}], "repeats": 1}],
}


def _hand_extract() -> dict:
    """Two decode ticks and a prefill-only tick, inside the harness's own
    tick annotations; two chips, the second busy throughout."""
    host = [
        ["bench.tick", 900, 11100],
        ["engine.tick", 1000, 11000],
        ["engine.decode", 1100, 2000],
        ["bench.decode_call", 1200, 1900],
        ["engine.sample", 2000, 6000],
        ["engine.wait", 2500, 5500],
        ["engine.commit", 6000, 9000],
        ["engine.wait", 8000, 8800],
        ["engine.record", 9000, 10000],
        ["engine.tick", 20000, 26000],
        ["engine.decode", 20100, 21000],
        ["engine.wait", 21500, 25000],
        ["engine.wait", 24000, 25500],  # overlapping waits count once
        ["engine.tick", 30000, 34000],  # no decode: not a decode tick
        ["engine.prefill", 30100, 33000],
        ["engine.wait", 31000, 32000],
    ]
    step = "jit__step(1234)"
    dev0 = {"modules": [[step, 1100, 9000], [step, 20100, 25900]],
            "ops": [["fusion.1", 0, 1500], ["grouped_mlp_kernel.3", 2200, 3200],
                    ["copy.2", 3000, 5400], ["copy.3", 7000, 8500], ["fusion.4", 10500, 21200],
                    ["grouped_mlp_kernel.3", 22000, 23000], ["fusion.5", 23000, 25800],
                    ["fusion.6", 27000, 40000]]}
    dev1 = {"modules": [[step, 1100, 9000], [step, 20100, 25900]],
            "ops": [["fusion.1", 0, 40000], ["grouped_mlp_kernel.3", 2200, 3200],
                    ["grouped_mlp_kernel.3", 22000, 23000]]}
    return {"host": host, "devices": {"0": dev0, "1": dev1}}


def _ctx(tr, ticks, conf=CONF, chips=2):
    return SimpleNamespace(trace=tr, ticks=ticks, conf=conf, chips=chips, peak=V5E, notes=[])


def _read(name, ctx):
    return catalog.metric_reader(name)(ctx)


TICKS = [{"tokens_this_tick": 0, "prefill_tokens": 32},
         {"tokens_this_tick": 4, "experts_routed": [3, 5]},
         {"tokens_this_tick": 4, "experts_routed": [2, 4]},
         {"tokens_this_tick": 4, "experts_routed": [4, 8]}]


def test_span_helpers_on_a_hand_built_extract():
    tr = _hand_extract()
    assert spans.decode_ticks(tr) == [(1000, 11000), (20000, 26000)]
    assert spans.host_ns(tr, (1000, 11000)) == 10000 - 3000 - 800
    assert spans.host_ns(tr, (20000, 26000)) == 6000 - 4000
    busy = spans.busy_intervals(tr["devices"]["0"])
    # busy in tick 1: 500 + 3200 + 1500 + 500; in tick 2: 1200 + 3800
    assert spans.idle_ns(busy, 1000, 11000) == 10000 - 5700
    assert spans.idle_ns(busy, 20000, 26000) == 6000 - 5000
    assert spans.idle_ns(busy, 41000, 42000) == 1000


def test_readers_on_a_hand_built_extract():
    tr = _hand_extract()
    assert _read("host_ms_per_tick.decode", _ctx(tr, TICKS)) == pytest.approx((6200 + 2000) / 2 / 1e6)
    # chip 0 idles 4300 and 1000 ns, chip 1 never
    assert _read("exposed_host_ms.decode", _ctx(tr, TICKS)) == pytest.approx(
        ((4300 + 1000) / 2 + 0) / 2 / 1e6)
    # two decode runs: the last two decode ticks of the log.  An expert's
    # weights are 64 x 256 in and out at 2 bytes; each layer reads 4 rows
    # and writes 4 outputs of 64 at 2 bytes
    expert = 64 * 256 * 2 * 2
    nbytes = (2 + 4 + 4 + 8) * expert + 2 * 2 * (4 * 2 * 64 * 2)
    nflops = 2 * 2 * (2 * 4 * 64 * 256 * 2)
    assert spans.grouped_mlp_work(CONF, TICKS[-2:]) == (nflops, nbytes)
    ctx = _ctx(tr, TICKS)
    share = _read("grouped_mlp_decode_roofline", ctx)
    # 2000 ns of kernel time per chip, the work split over the two chips
    assert share == pytest.approx(100 * (nbytes / 2 / V5E["hbm_bytes_s"]) / 2e-6)
    assert "memory-bound" in ctx.notes[-1]


def test_readers_find_nothing_in_a_program_without_spans():
    tr = _hand_extract()
    tr["host"] = [h for h in tr["host"] if not h[0].startswith("engine.")]
    untagged = [{k: v for k, v in m.items() if k != "experts_routed"} for m in TICKS]
    for name in READERS:
        assert _read(name, _ctx(tr, untagged)) is None
    # a counter with the wrong number of layers is not read
    assert _read("grouped_mlp_decode_roofline",
                 _ctx(_hand_extract(), [{"tokens_this_tick": 4, "experts_routed": [1]}] * 2)) is None


def test_readers_on_recorded_chip_trace():
    """A few ticks of a tiny configuration on one v5e, the engine writing
    its spans and counter: the trace's extract and the engine's tick log."""
    rec = json.loads((ROOT / "bench" / "testdata" / "ticks_spans_small.json").read_text())
    tr = trace.load(ROOT / "bench" / "testdata" / "trace_spans_small.json.gz")
    ctx = _ctx(tr, rec["ticks"], conf=rec["conf"], chips=rec["chips"])
    ticks = spans.decode_ticks(tr)
    assert len(ticks) >= 3
    for t in ticks:
        assert 0 < spans.host_ns(tr, t) < t[1] - t[0]
    host = _read("host_ms_per_tick.decode", ctx)
    exposed = _read("exposed_host_ms.decode", ctx)
    assert 0 < host < max(e - s for s, e in ticks) / 1e6
    assert 0 <= exposed < max(e - s for s, e in ticks) / 1e6
    share = _read("grouped_mlp_decode_roofline", ctx)
    assert share is not None and 0 < share <= 105
    # every traced decode tick's routed experts are in the tick log
    assert all("experts_routed" in m for m in rec["ticks"] if m["tokens_this_tick"])
