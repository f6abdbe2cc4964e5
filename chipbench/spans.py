"""The per-layer reduction of a traced run's profiler trace, from what
the serving program itself writes into it.

With its telemetry on, the program marks each engine step
(``engine.step``), each dispatch's enqueue (``engine.dispatch.<kind>``,
with the work it carries as integer arguments: ``rows``, ``keys`` and
``new_tokens`` today), each device-to-host read (``engine.readback``),
its other phases (``engine.*``, and ``cluster.*`` in a cluster) as host
annotations, and names the layers of its device programs with
``jax.named_scope``, which the profiler keeps in each device
operation's ``op_name`` metadata. Which names are layer scopes is the
configuration's family's to say (``families.Needed.scopes``).

:func:`extract` keeps from one ``.xplane.pb`` file what this reduction
and ``trace.reduce`` read, as events ``[plane, line, name, start_ns,
duration_ns, args]``: device operations with their scope path (``args``
``{"op": path}``, from the operation metadata the trace keeps), device
programs, and the host spans whose names start with ``chipbench.``,
``engine.`` or ``cluster.`` with every integer argument the program gave
them. A program loaded from JAX's persistent compilation cache carries
the op names of the tree that compiled it: the cache key leaves debug
information out, so a program that differs only in its scopes is served
from an entry compiled without them (a traced run compiles its own,
``run.py``).
:func:`reduce` computes over the ``chipbench.window`` stretch:

- ``device_s``: device seconds of operations by program kind
  (``trace.kind_of``) and scope, the innermost of the family's scopes
  on the operation's path, else ``unscoped`` (never dropped);
  control-flow operations are left out, as in ``trace.reduce``;
- ``dispatch``: by dispatch kind, the number of dispatches enqueued in
  the stretch (``n``) and the sum of each integer argument of their
  spans, identifiers (:data:`IDS`) aside;
- ``host_s``: by host span name, the duration of every span that
  started in the stretch (seconds);
- ``steps`` and ``host_self_s``: the ``engine.step`` spans inside the
  stretch and their mean host time outside their ``engine.readback``
  children (the host's own work per step, not its waits on the device).

The metric functions below turn that into shares of a roofline (at the
peaks of ``peaks.py``, with the family's needed work) and the host's
self time per step.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

from chipbench import trace as TR

UNSCOPED = "unscoped"
STEP_SPAN = "engine.step"
READBACK_SPAN = "engine.readback"
DISPATCH_PREFIX = "engine.dispatch."
# integer span arguments that name something rather than count work
IDS = ("rid",)
TF_OP_STAT = "tf_op"


def extract(path) -> list:
    """Events of one ``.xplane.pb`` file that :func:`reduce` reads; an
    operation's scope path is its ``op_name`` (:func:`op_names`)."""
    import jax
    data = Path(path).read_bytes()
    paths = op_names(data)
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:CPU")
        if not (dev or host):
            continue
        for line in plane.lines:
            if dev and line.name not in TR._DEVICE_LINES:
                continue
            for e in line.events:
                if host and not e.name.startswith(TR._HOST_PREFIXES):
                    continue
                args = {}
                if host:        # the profiler's own stats start with "_"
                    args = {k: v for k, v in e.stats
                            if isinstance(v, int) and not isinstance(v, bool)
                            and not k.startswith("_")}
                elif (plane.name, e.name) in paths:
                    args = {"op": paths[(plane.name, e.name)]}
                out.append([plane.name, line.name if dev else "host",
                            e.name, float(e.start_ns), float(e.duration_ns),
                            args])
    return out


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one serialized protobuf
    message: an int for a varint, a byte slice for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def op_names(data) -> dict:
    """(device plane, operation event name) -> the operation's
    ``op_name`` path, from the ``tf_op`` stat the device keeps with each
    operation's metadata in a serialized ``XSpace`` (the profiler's
    ``ProfileData`` does not expose metadata stats). Field numbers are
    those of ``xplane.proto``: XSpace.planes 1; XPlane.name 2,
    event_metadata 4, stat_metadata 5 (map entries: key 1, value 2);
    XEventMetadata.name 2, stats 5; XStatMetadata.id 1, name 2;
    XStat.metadata_id 1, str_value 5, ref_value 7 (the id of a stat
    metadata entry whose name is the text)."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        fields = list(_fields(plane)) if f == 1 else []
        pname = next((_text(v) for k, v in fields if k == 2), "")
        if not pname.startswith("/device:"):
            continue
        names = {}
        for k, entry in fields:
            if k == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                names[meta.get(1, 0)] = _text(meta.get(2, b""))
        ids = {i for i, n in names.items() if n == TF_OP_STAT}
        for k, entry in fields if ids else ():
            if k != 4:
                continue
            name = path = None
            for mk, mv in _fields(dict(_fields(entry)).get(2, b"")):
                if mk == 2:
                    name = _text(mv)
                elif mk == 5:
                    stat = dict(_fields(mv))
                    if stat.get(1) in ids:
                        path = (_text(stat[5]) if 5 in stat
                                else names.get(stat.get(7)))
            if name is not None and path is not None:
                out[(pname, name)] = path
    return out


def scope_of(op_path: str | None, scopes) -> str:
    """The innermost of ``scopes`` on an operation's ``op_name`` path."""
    for part in reversed((op_path or "").split("/")):
        part = part.split(":")[0]         # a device's "name:type" form
        if part in scopes:
            return part
    return UNSCOPED


def _window(events):
    win = [(e[3], e[3] + e[4]) for e in events
           if e[1] == "host" and e[2] == TR.WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {TR.WINDOW_SPAN!r} span in the trace")
    return win[0]


def reduce(events: list, scopes=()) -> dict:
    """The stretch's per-layer numbers (see the module docstring), with
    device time by the layer ``scopes`` of the configuration's family.
    Raises ``ValueError`` when the stretch or the device is missing."""
    lo, hi = _window(events)
    by_plane = defaultdict(lambda: {"ops": [], "mods": []})
    host = []
    for plane, line, name, s, d, args in events:
        if line == "XLA Ops":
            by_plane[plane]["ops"].append((s, s + d, name, args.get("op")))
        elif line == "XLA Modules":
            by_plane[plane]["mods"].append((s, s + d, name))
        elif line == "host":
            host.append((s, s + d, name, args))
    chips = sorted(p for p, v in by_plane.items() if v["ops"])
    if not chips:
        raise ValueError("no device operations in the trace")
    device = defaultdict(lambda: defaultdict(float))
    for plane in chips:
        mods = sorted(by_plane[plane]["mods"])
        j = 0
        for s, e, name, path in sorted(by_plane[plane]["ops"],
                                       key=lambda o: o[:2]):
            cs, ce = max(s, lo), min(e, hi)
            if ce <= cs:
                continue
            while j < len(mods) and mods[j][1] <= s:
                j += 1
            prog = (TR.program_name(mods[j][2])
                    if j < len(mods) and mods[j][0] <= s else "?")
            if TR.op_name(name).split(".")[0] in TR._CONTAINERS:
                continue
            device[TR.kind_of(prog)][scope_of(path, scopes)] += \
                (ce - cs) * 1e-9
    n = len(chips)
    dispatch = defaultdict(lambda: defaultdict(int))
    host_s = defaultdict(list)
    steps, readbacks = [], []
    for s, e, name, args in host:
        if not lo <= s < hi or name == TR.WINDOW_SPAN:
            continue
        host_s[name].append((e - s) * 1e-9)
        if name.startswith(DISPATCH_PREFIX):
            d = dispatch[name[len(DISPATCH_PREFIX):]]
            d["n"] += 1
            for k, v in args.items():
                if k not in IDS:
                    d[k] += int(v)
        elif name == STEP_SPAN and e <= hi:
            steps.append((s, e))
        elif name == READBACK_SPAN:
            readbacks.append((s, e))
    self_s = []
    for s, e in steps:
        waited = sum(b - a for a, b in readbacks if s <= a and b <= e)
        self_s.append((e - s - waited) * 1e-9)
    return {
        "window_s": (hi - lo) * 1e-9,
        "device_s": {k: {sc: v / n for sc, v in d.items()}
                     for k, d in device.items()},
        "dispatch": {k: dict(v) for k, v in dispatch.items()},
        "host_s": dict(host_s),
        "steps": len(steps),
        "host_self_s": sum(self_s) / len(self_s) if self_s else None,
    }


def unscoped_share(red: dict, kind: str) -> float | None:
    """Percent of ``kind`` programs' operation time in no layer scope."""
    d = red["device_s"].get(kind)
    total = sum(d.values()) if d else 0.0
    return 100.0 * d.get(UNSCOPED, 0.0) / total if total > 0 else None


def decode_attention_roofline(red: dict, needed, peaks: dict):
    """Least time of the decode dispatches' attention (the family's
    ``decode_attention_work`` of their summed ``keys`` and ``rows``)
    over the device time of ``attention``-scoped operations in decode
    programs, in percent."""
    d = red["dispatch"].get("decode")
    t = red["device_s"].get("decode", {}).get("attention", 0.0)
    if not d or t <= 0 or "keys" not in d:
        return None
    w = needed.decode_attention_work(d["keys"], d["rows"])
    return 100.0 * w.least_seconds(peaks["bf16_flops"],
                                   peaks["hbm_bytes_s"]) / t


def kv_write_roofline(red: dict, needed, peaks: dict):
    """Least time of writing every dispatch's new keys and values once
    (the family's ``kv_write_work`` of the summed ``new_tokens``) over
    the device time of ``kv_write``-scoped operations in all programs,
    in percent."""
    n = sum(d.get("new_tokens", 0) for d in red["dispatch"].values())
    t = sum(v.get("kv_write", 0.0) for v in red["device_s"].values())
    if n <= 0 or t <= 0:
        return None
    w = needed.kv_write_work(n)
    return 100.0 * w.least_seconds(peaks["bf16_flops"],
                                   peaks["hbm_bytes_s"]) / t


def host_self_ms(red: dict):
    """Mean host time per engine step outside its readbacks (ms)."""
    s = red["host_self_s"]
    return None if s is None else 1e3 * s
