"""Profiler trace of a traced run, reduced to what the per-layer readers use.

The run writes the JAX profiler's ``.xplane.pb``; ``extract`` reads it with
``jax.profiler.ProfileData`` into a plain dict — for each device, the
program executions (``XLA Modules`` line) and the operations (``XLA Ops``
line) as ``[name, start_ns, end_ns]``, and the interpreter thread's host
events — which is what a test keeps as a small recorded trace.  Everything
after that is interval arithmetic on the extract.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_LINE = re.compile(r"^python")  # the interpreter's thread: the harness and the engine
# Operations that contain others (a scan's loop, a branch, a call): their
# time is their body's, so they count toward neither busy time nor any sum.
CONTAINER = re.compile(r"^(while|conditional|call)\b")

# The program's jitted entries, by the module names XLA gives them (the
# name of the jitted Python function): the engine's paged decode step and
# its batched chunk prefill.
PROGRAMS = {"decode": re.compile(r"^jit__step\b"),
            "prefill": re.compile(r"^jit__prefill_chunk_batched_fn\b")}
# The Pallas kernels of the main path, matched on an operation's name.  The
# kernels pass no ``name=``; the custom call takes the name of the Python
# function that calls ``pallas_call``.
KERNELS = {"paged_decode_attn": re.compile(r"^paged_decode_attention\b"),
           "prefill_attn": re.compile(r"^paged_prefill_attention\b"),
           "grouped_mlp": re.compile(r"^grouped_mlp(_quant)?(_kernel)?\b")}


def extract(logdir: str) -> dict:
    """The newest ``.xplane.pb`` under ``logdir`` as a plain dict.  An
    operation's event is named by its whole HLO text; the extract keeps the
    instruction's name (``paged_decode_attention.44``), and drops the
    operations that contain others."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = e.name.split(" = ")[0].lstrip("%") if key == "ops" else e.name
                    if key == "ops" and CONTAINER.match(name):
                        continue
                    dev[key].append([name, int(e.start_ns), int(e.start_ns + e.duration_ns)])
            out["devices"][m.group(1)] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if not HOST_LINE.match(line.name):
                    continue
                for e in line.events:
                    out["host"].append([e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)])
    return out


def save(tr: dict, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def load(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def merged(intervals) -> list:
    iv = sorted((s, e) for s, e in intervals if e > s)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    return sum(e - s for s, e in merged(intervals))


def program_events(dev: dict, program: str) -> list:
    pat = PROGRAMS[program]
    return [e for e in dev["modules"] if pat.search(e[0])]


def ops_within(dev: dict, spans) -> list:
    """Operations that start inside one of the ``spans`` (program runs)."""
    spans = sorted((s, e) for _, s, e in spans)
    if not spans:
        return []
    starts = np.array([s for s, _ in spans])
    ends = np.array([e for _, e in spans])
    out = []
    for op in dev["ops"]:
        i = np.searchsorted(starts, op[1], side="right") - 1
        if i >= 0 and op[1] < ends[i]:
            out.append(op)
    return out


def is_kernel(op, kernel: str) -> bool:
    return bool(KERNELS[kernel].match(op[0]))


def base_name(name: str) -> str:
    """``copy.501.remat`` -> ``copy``: an operation's kind, for the breakdown."""
    return re.sub(r"(\.(\d+|remat\d*|remat_\w+|clone))+$", "", name)


def busy_ns(dev: dict) -> int:
    """Time in which some operation ran on the device."""
    return union_ns((s, e) for _, s, e in dev["ops"])


def top_ops(tr: dict, n: int = 10) -> list:
    """The device operations that took most time, by name, averaged over
    devices: ``[[name, seconds], ...]``."""
    tot = {}
    for dev in tr["devices"].values():
        for name, s, e in dev["ops"]:
            key = base_name(name)
            tot[key] = tot.get(key, 0) + (e - s)
    nd = max(1, len(tr["devices"]))
    return [[k, v / nd / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: dict, n: int = 10) -> list:
    """The longest idle gaps of device 0, each named by the innermost host
    event that covers its middle: ``[[what the host did, seconds], ...]``."""
    devs = tr["devices"]
    if not devs:
        return []
    dev = devs[min(devs, key=int)]
    busy = merged((s, e) for _, s, e in dev["ops"])
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = sorted(tr["host"], key=lambda h: h[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        cover = [h for h in host if h[1] <= mid < h[2]]
        what = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "no host event"
        out.append([what, (e - s) / 1e9])
    return out
