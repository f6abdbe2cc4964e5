"""The reduction from a ``jax.profiler`` trace to the numbers the
per-layer metrics read.

A traced run records one steady stretch, marked on the host by the
annotation ``chipbench.window`` and started and stopped between engine
steps (every step ends by reading its sampled tokens back, so no device
work of a step outside the stretch falls in it). ``spans.extract`` keeps
what the reduction needs from the ``.xplane.pb`` file, as plain events
``[plane, line, name, start_ns, duration_ns, ...]``: device operations
and programs (lines ``XLA Ops`` and ``XLA Modules`` of each ``/device:``
plane) and the host spans of the benchmark and the program (names
starting with ``chipbench.``, ``engine.`` or ``cluster.``); what follows
the duration is not read here. :func:`reduce` computes from them:

- ``busy_s``: the union of device operation intervals in the stretch,
  averaged over the chips; ``window_s``: the stretch's length;
- ``programs``: device seconds and executions of each program, by its
  name with ``jit_`` and numeric suffixes removed, and ``kinds``: the
  same summed by dispatch kind (:func:`kind_of`); a program that no
  kind claims is counted under ``unattributed``, never dropped;
- ``device_ops``: device seconds by ``kind/program/operation``, largest
  first; control-flow operations (``while``, ``conditional``, ``call``)
  are left out of it, since their time is that of the operations inside
  them;
- ``idle_gaps``: idle device seconds in the stretch by what the host
  was doing: the innermost host span over the middle of each gap.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "chipbench.window"
_HOST_PREFIXES = ("chipbench.", "engine.", "cluster.")
_DEVICE_LINES = ("XLA Ops", "XLA Modules")
_CONTAINERS = ("while", "conditional", "call")

# program name (jit_ and numeric suffixes removed) -> dispatch kind
KINDS = {"decode": "decode", "prefill": "prefill",
         "chunk_paged": "chunk_paged",
         "chunk_contiguous": "chunk_contiguous",
         "_sample": "sampling"}
KV_PROGRAMS = ("splice_rows", "splice_chunk", "splice_blocks",
               "splice_positions", "gather_blocks")


def program_name(module: str) -> str:
    name = re.sub(r"\(\d+\)$", "", module.strip())
    name = re.sub(r"(\.\d+)+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An operation's HLO name (``fusion.12``) from its trace event name,
    which may hold the whole instruction text."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def kind_of(program: str) -> str:
    if program in KINDS:
        return KINDS[program]
    if program in KV_PROGRAMS:
        return "kv_update"
    return "unattributed"


def find_xplane(directory) -> Path:
    hits = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(hits[-1])


def _union(intervals):
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(events: list) -> dict:
    """The trace's numbers for the stretch (see the module docstring).
    Raises ``ValueError`` when the stretch or the device is missing."""
    spans = [(e[3], e[3] + e[4], e[2]) for e in events if e[1] == "host"]
    win = [(s, t) for s, t, n in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win[0]
    by_plane = defaultdict(lambda: {"ops": [], "mods": []})
    for plane, line, name, s, d, *_ in events:
        if line == "XLA Ops":
            by_plane[plane]["ops"].append((s, s + d, name))
        elif line == "XLA Modules":
            by_plane[plane]["mods"].append((s, s + d, name))
    chips = sorted(p for p, v in by_plane.items() if v["ops"] or v["mods"])
    if not chips:
        raise ValueError("no device plane in the trace")
    busy, programs, ops = [], defaultdict(lambda: [0.0, 0]), \
        defaultdict(float)
    gaps_first = None
    for plane in chips:
        v = by_plane[plane]
        src = v["ops"] or v["mods"]
        iv = []
        for s, e, _ in src:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                iv.append((s, e))
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged))
        if gaps_first is None:
            gaps_first, prev = [], lo
            for s, e in merged:
                if s > prev:
                    gaps_first.append((prev, s))
                prev = e
            if hi > prev:
                gaps_first.append((prev, hi))
        mods = sorted(v["mods"])
        for s, e, name in mods:
            cs, ce = _clip(s, e, lo, hi)
            if ce > cs:
                p = programs[program_name(name)]
                p[0] += (ce - cs) * 1e-9
                p[1] += 1
        # each op belongs to the program running around its start
        j = 0
        for s, e, name in sorted(v["ops"]):
            cs, ce = _clip(s, e, lo, hi)
            if ce <= cs:
                continue
            while j < len(mods) and mods[j][1] < s:
                j += 1
            prog = (program_name(mods[j][2])
                    if j < len(mods) and mods[j][0] <= s else "?")
            op = op_name(name)
            if op.split(".")[0] not in _CONTAINERS:
                ops[f"{kind_of(prog)}/{prog}/{op}"] += (ce - cs) * 1e-9
    kinds = defaultdict(float)
    for name, (sec, _) in programs.items():
        kinds[kind_of(name)] += sec
    idle = defaultdict(float)
    # host spans nest, so the innermost one over an instant is the one
    # that started last among those still open then
    host = sorted((s, t, n) for s, t, n in spans if n != WINDOW_SPAN)
    starts = [h[0] for h in host]
    for s, e in gaps_first:
        mid = 0.5 * (s + e)
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and host[i][1] < mid:
            i -= 1
        idle[host[i][2] if i >= 0 else "no host span"] += (e - s) * 1e-9
    n = len(chips)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "chips": n,
        "programs": {k: {"device_s": v[0] / n, "executions": v[1] // n}
                     for k, v in programs.items()},
        "kinds": {k: v / n for k, v in kinds.items()},
        "device_ops": sorted(([k, v / n] for k, v in ops.items()),
                             key=lambda x: -x[1]),
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda x: -x[1]),
    }


def save_events(events: list, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)
