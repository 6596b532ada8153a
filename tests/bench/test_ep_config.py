"""CPU tests of the expert-parallel configuration (``nlg-350m-moe128-ep4``'s
shape at a tiny size): a MoE-128-shaped model, with no residual MLP and 16
experts, 4 on each of four host CPU devices, in one child process
(``ep_config_run.py``) whose three checks are read here:

* served through paged batched prefill and decode on the (4,) mesh, its
  kept logits agree with the plain float32 reference;
* the shares add up: with the exchange left out, the four devices' partial
  MoE outputs sum to the single-device layer's output;
* the engine's ``ep_rows`` counter sums to the decode rows in every MoE
  layer and matches, on the host, the rows per expert of the step's own
  routing statistics summed by device.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

NAME = "tiny-moe16-ep4"
CONF = {
    "name": NAME, "source": "https://arxiv.org/abs/2201.05596",
    "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 16, "intermediate_size": 256,
    "vocab_size": 512, "max_seq_len": 2048, "num_hidden_layers": 4, "act": "gelu", "top_k": 1,
    "rms_eps": 1e-6, "rope_theta": 10000.0, "tie_embeddings": True, "param_dtype": "bfloat16",
    "segments": [{"pattern": [{"ffn": "dense"}, {"ffn": "moe", "experts": 16, "residual": False}],
                  "repeats": 2}],
    "builder": {"fn": "repro.core.prmoe:nlg_moe", "args": [NAME, 4, 64, 4, 16],
                "kwargs": {"residual": False, "vocab": 512}},
    "serving": {"chips": 4, "ep_mesh": [4], "moe_impl": "grouped", "slots": 8, "capacity": 128,
                "page_size": 16, "prefill_chunk": 32, "n_pages": 48},
}
# The served path keeps activations and K/V in bfloat16 where the reference
# keeps float32 (both use the same bfloat16-rounded weights), so a served
# logit departs from the reference's by bf16 rounding carried through four
# layers: sound runs read 0.0024-0.0038 at the 90th percentile on the
# one-chip tiny cell (tests/bench/test_bench.py), and this tolerance is that
# cell's limit; leaving out the exchange reads 0.050 and more.
TOLERANCE = 0.012


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    path = tmp_path_factory.mktemp("ep_config") / f"{NAME}.json"
    path.write_text(json.dumps(CONF))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(Path(__file__).parent / "ep_config_run.py"), str(path),
                          str(2**31 + 77)], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _served(res):
    assert res["served"]["served_logit_err_p90"] <= TOLERANCE, res["served"]


def _shares(res):
    s = res["shares"]
    assert s["parts"] == 4 and s["devices_contributing"] > 1, s
    # top-1 routing: each token's output comes from one device and the others
    # give exact zeros, so the sum is the single-device layer to bf16 rounding
    assert s["sum_vs_single"] <= 2**-7 * s["single_max"], s
    assert s["whole_vs_single"] <= 2**-7 * s["single_max"], s


def _ep_rows(res):
    r = res["ep_rows"]
    assert r["ticks"] == r["replayed"] > 0, r
    moe_layers = 2
    for rows in r["got"]:
        assert len(rows) == moe_layers and all(len(x) == 4 for x in rows)
        # every slot row routes, active or not: the step reads those experts
        assert all(sum(x) == r["slots"] for x in rows)
    assert r["got"] == r["want"]
    # the rows did spread over more than one device at least once
    assert any(sum(np.asarray(x) > 0) > 1 for rows in r["got"] for x in rows)


@pytest.mark.parametrize("check", [_served, _shares, _ep_rows], ids=["served", "shares", "ep_rows"])
def test_ep_configuration(result, check):
    check(result)
