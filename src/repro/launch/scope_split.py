"""Device time of one jitted program, split by the model's named scopes.

The model names its parts with ``jax.named_scope`` (``SCOPES``).  On the
TPU a profiler trace's operation events carry only the HLO instruction's
name (``fusion.123``), not its scope; the scope is in the compiled
program's ``op_name`` metadata.  This tool joins the two: each operation
the device ran inside one of the program's executions is charged to the
innermost model scope of its instruction's ``op_name``, and the device
time per execution is summed by scope and, within a scope, by operation
kind (``copy``, ``fusion``, a kernel's name).

Two rows hold what no scope covers: ``(no op_name)``, instructions XLA
inserted with no source line (loop-carry copies, relayouts), and
``(no scope)``, source lines outside every model scope (a layer scan's own
slicing of its stacked carries).  A fusion carries its root instruction's
``op_name``, so it is charged to the scope of its last step.

The compiled text must be the program that ran, so that instruction names
match: ``jitted.lower(*args).compile().as_text()`` in the process that
traced it, or XLA's dump of it (``XLA_FLAGS=--xla_dump_to=DIR``, the
module's ``*after_optimizations.txt``).  From Python::

    text = engine._decode.lower(*args).compile().as_text()
    print(format_split(*split_program(logdir, text)))

From the shell, on a profile directory and a saved compiled text::

    python -m repro.launch.scope_split --trace LOGDIR --hlo STEP.txt \\
        [--module '^jit__prefill_chunk_batched_fn\\b']
"""
from __future__ import annotations

import argparse
import glob
import re
from pathlib import Path
from typing import Dict, List, Tuple

# The model's named scopes (models/, core/, serving/sampling.py), and the
# EP exchange's collectives (core/moe_serve.py).
SCOPES = ("attn", "kv_write", "moe_gate", "moe_dispatch", "expert_mlp",
          "moe_combine", "residual_mlp", "mlp", "logits", "sample", "ep_exchange")
NO_OP_NAME = "(no op_name)"
NO_SCOPE = "(no scope)"
# The engine's paged decode step, by the module name XLA gives it.
DECODE_MODULE = r"^jit__step\b"
# Operations that contain others (a loop, a branch, a call): their time is
# their body's, so they are not counted themselves.
_CONTAINER = re.compile(r"^(while|conditional|call)\b")
_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"', re.M)

Split = Dict[str, Dict[str, float]]  # scope -> {kind: ms per execution}


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name``, from compiled HLO text."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def scope_of(op_name: str | None) -> str:
    """The innermost model scope on an ``op_name`` path."""
    if op_name is None:
        return NO_OP_NAME
    return next((p for p in reversed(op_name.split("/")) if p in SCOPES), NO_SCOPE)


def kind_of(name: str) -> str:
    """``copy.501``, ``fusion.722.remat_uncompressed`` -> ``copy``, ``fusion``."""
    return re.sub(r"(\.(\d+|remat\d*|remat_\w+|clone))+$", "", name)


def device_events(logdir: str, module: str = DECODE_MODULE
                  ) -> Tuple[List[Tuple[int, int]], List[Tuple[str, int, int]]]:
    """From the newest ``.xplane.pb`` under ``logdir``: the executions of
    the program whose module name matches ``module`` on the first TPU, as
    ``(start_ns, end_ns)``, and every operation on that device as
    ``(instruction name, start_ns, end_ns)``, containers left out."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    want = re.compile(module)
    runs, ops = [], []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            for e in line.events:
                span = (int(e.start_ns), int(e.start_ns + e.duration_ns))
                if line.name == "XLA Modules" and want.search(e.name):
                    runs.append(span)
                elif line.name == "XLA Ops":
                    name = e.name.split(" = ")[0].lstrip("%")
                    if not _CONTAINER.match(name):
                        ops.append((name, *span))
    return runs, ops


def split(runs: List[Tuple[int, int]], ops: List[Tuple[str, int, int]],
          names: Dict[str, str]) -> Split:
    """Device ms per execution, by scope and kind, of the operations that
    start inside an execution in ``runs``."""
    out: Split = {}
    for name, start, end in ops:
        if not any(s <= start < e for s, e in runs):
            continue
        kinds = out.setdefault(scope_of(names.get(name)), {})
        kind = kind_of(name)
        kinds[kind] = kinds.get(kind, 0.0) + (end - start) / 1e6 / max(len(runs), 1)
    return out


def split_program(logdir: str, hlo_text: str, module: str = DECODE_MODULE
                  ) -> Tuple[Split, int]:
    """``split`` of a profile directory against the compiled program's
    text; also returns the number of executions found."""
    runs, ops = device_events(logdir, module)
    return split(runs, ops, op_names(hlo_text)), len(runs)


def format_split(by: Split, n_runs: int) -> str:
    rows = sorted(by.items(), key=lambda kv: -sum(kv[1].values()))
    total = sum(sum(k.values()) for k in by.values())
    lines = [f"{n_runs} executions, {total:.3f} ms of operations per execution",
             f"{'scope':<16} {'ms':>9}  largest kinds (ms)"]
    for scope, kinds in rows:
        big = sorted(kinds.items(), key=lambda kv: -kv[1])[:4]
        lines.append(f"{scope:<16} {sum(kinds.values()):>9.3f}  "
                     + ", ".join(f"{k} {v:.3f}" for k, v in big))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", required=True, help="profile directory (holds a .xplane.pb)")
    ap.add_argument("--hlo", required=True, help="the program's compiled HLO text")
    ap.add_argument("--module", default=DECODE_MODULE,
                    help="regex on the program's module name (default: the decode step)")
    a = ap.parse_args(argv)
    by, n = split_program(a.trace, Path(a.hlo).read_text(), a.module)
    if not n:
        print(f"no execution of a module matching {a.module!r} on TPU 0")
        return 1
    print(format_split(by, n))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
