"""``launch/scope_split.py``: the compiled decode step's ``op_name``
metadata carries the model's named scopes, and the join charges each
operation's device time to the innermost scope on its path."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.core.prmoe import nlg_moe
from repro.launch import scope_split
from repro.models.model import init_params
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Request


@pytest.fixture(scope="module")
def decode_text():
    """The compiled text of a tiny paged PR-MoE engine's decode step, lowered
    with the arguments it was called with."""
    cfg = nlg_moe("scopes-tiny", 2, 64, 4, 4, residual=True, vocab=256).replace(moe_impl="grouped")
    eng = ContinuousEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)), slots=2,
                           capacity=32, paged=True, page_size=8, prefill_chunk=16)
    step, seen = eng._decode, []

    def capture(*args):
        seen.append(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
        return step(*args)

    eng._decode = capture
    eng.submit(Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=3))
    eng.run_until_done()
    assert seen
    return step.lower(*seen[0]).compile().as_text()


def test_decode_step_carries_the_scopes(decode_text):
    scopes = {scope_split.scope_of(o) for o in scope_split.op_names(decode_text).values()}
    want = {"attn", "kv_write", "moe_gate", "moe_dispatch", "expert_mlp", "moe_combine",
            "residual_mlp", "mlp", "logits"}
    assert want <= scopes, want - scopes


def test_scope_of_takes_the_innermost_scope():
    assert scope_split.scope_of("jit(_step)/while/body/attn/kv_write/scatter") == "kv_write"
    assert scope_split.scope_of("jit(_step)/while/body/dynamic_slice") == scope_split.NO_SCOPE
    assert scope_split.scope_of(None) == scope_split.NO_OP_NAME
    assert scope_split.kind_of("copy.501") == "copy"
    assert scope_split.kind_of("grouped_mlp_kernel.3.7") == "grouped_mlp_kernel"
    assert scope_split.kind_of("fusion.722.remat_uncompressed") == "fusion"
    assert scope_split.kind_of("dynamic-slice_bitcast_fusion.19.remat2") == "dynamic-slice_bitcast_fusion"


def test_split_charges_each_op_to_its_scope():
    text = """
ENTRY %main.2 (x.1: f32[8,8]) -> f32[8] {
  %copy.4 = f32[8,8]{1,0} copy(%x.1)
  %dot.1 = f32[8,8]{1,0} dot(%x.1, %x.1), metadata={op_name="jit(_step)/attn/dot_general" stack_frame_id=4}
  ROOT %fusion.7 = f32[8]{0} fusion(%dot.1), kind=kLoop, metadata={op_name="jit(_step)/while/body/reduce_sum"}
}
"""
    names = scope_split.op_names(text)
    assert names == {"dot.1": "jit(_step)/attn/dot_general",
                     "fusion.7": "jit(_step)/while/body/reduce_sum"}
    runs = [(0, 10_000_000), (20_000_000, 30_000_000)]
    ops = [("dot.1", 1_000_000, 4_000_000), ("dot.1", 21_000_000, 22_000_000),
           ("fusion.7", 5_000_000, 6_000_000), ("copy.4", 22_000_000, 24_000_000),
           ("dot.1", 12_000_000, 19_000_000)]  # between the runs: not counted
    by = scope_split.split(runs, ops, names)
    assert by == {"attn": {"dot": 2.0}, scope_split.NO_SCOPE: {"fusion": 0.5},
                  scope_split.NO_OP_NAME: {"copy": 1.0}}
    assert "attn" in scope_split.format_split(by, len(runs))


def test_cli_finds_no_tpu_program_in_a_cpu_profile(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x) * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path / "prof"))
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    hlo = tmp_path / "step.txt"
    hlo.write_text(f.lower(jnp.ones(4)).compile().as_text())
    assert scope_split.device_events(str(tmp_path / "prof")) == ([], [])
    assert scope_split.main(["--trace", str(tmp_path / "prof"), "--hlo", str(hlo)]) == 1
    with pytest.raises(FileNotFoundError):
        scope_split.device_events(str(tmp_path / "nothing"))
