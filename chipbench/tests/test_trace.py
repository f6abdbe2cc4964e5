"""The reduction from trace events to the numbers the per-layer metrics
read: busy union, device time per program and kind, and idle gaps
attributed to the host span around them."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from chipbench import trace as TR

D = "/device:TPU:0"


def _ev(line, name, start, dur, plane=D):
    return [plane, line, name, float(start), float(dur)]


def _host(name, start, dur):
    return ["/host:CPU", "host", name, float(start), float(dur)]


def _synthetic():
    # stretch 1000..2000 ns; two decode modules and a splice, ops inside
    return [
        _host(TR.WINDOW_SPAN, 1000, 1000),
        _host("chipbench.step", 1000, 500),
        _host("engine.decode", 1050, 300),
        _host("chipbench.wait", 1600, 400),
        _ev("XLA Modules", "jit_decode(12)", 900, 300),     # 200 in window
        _ev("XLA Ops", "fusion.1", 900, 150),
        _ev("XLA Ops", "fusion.2", 1050, 150),
        _ev("XLA Modules", "jit_splice_positions(3)", 1300, 100),
        _ev("XLA Ops", "dynamic-update-slice", 1300, 100),
        _ev("XLA Modules", "jit_decode(12)", 1500, 100),
        _ev("XLA Ops", "fusion.1", 1500, 60),
        _ev("XLA Ops", "fusion.2", 1540, 60),                 # overlaps
        _ev("XLA Modules", "jit_mystery", 1950, 100),         # 50 in window
        _ev("XLA Ops", "copy", 1950, 100),
    ]


def test_busy_union_programs_and_gaps():
    red = TR.reduce(_synthetic())
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: [1000,1200] + [1300,1400] + [1500,1600] + [1950,2000]
    assert red["busy_s"] == pytest.approx(450e-9)
    assert red["programs"]["decode"]["device_s"] == pytest.approx(300e-9)
    assert red["programs"]["decode"]["executions"] == 2
    assert red["kinds"]["decode"] == pytest.approx(300e-9)
    assert red["kinds"]["kv_update"] == pytest.approx(100e-9)
    assert red["kinds"]["unattributed"] == pytest.approx(50e-9)
    gaps = dict(red["idle_gaps"])
    # 1200-1300 lies in engine.decode (innermost), 1400-1500 in
    # chipbench.step, 1600-1950 in chipbench.wait
    assert gaps["engine.decode"] == pytest.approx(100e-9)
    assert gaps["chipbench.step"] == pytest.approx(100e-9)
    assert gaps["chipbench.wait"] == pytest.approx(350e-9)
    ops = dict(red["device_ops"])
    assert ops["decode/decode/fusion.2"] == pytest.approx(210e-9)
    assert ops["unattributed/mystery/copy"] == pytest.approx(50e-9)


def test_program_names_and_kinds():
    assert TR.program_name("jit_chunk_paged(7)") == "chunk_paged"
    assert TR.program_name("jit__sample.3") == "_sample"
    assert TR.kind_of("_sample") == "sampling"
    assert TR.kind_of("splice_rows") == "kv_update"
    assert TR.kind_of("something_new") == "unattributed"


def test_missing_stretch_or_device_is_an_error():
    with pytest.raises(ValueError):
        TR.reduce([e for e in _synthetic() if e[2] != TR.WINDOW_SPAN])
    with pytest.raises(ValueError):
        TR.reduce([e for e in _synthetic() if e[1] == "host"])


DATA = Path(__file__).resolve().parent / "data"
RECORDED = sorted(DATA.glob("*.v5e.json.gz"))


def _busy_on_a_grid(events, lo, hi, step=100.0):
    """Busy device time counted on a 100 ns grid: an independent check
    of the interval union."""
    n = int((hi - lo) / step) + 1
    grid = np.zeros(n, bool)
    for plane, line, name, s, d in events:
        if line != "XLA Ops":
            continue
        a = int(max(0.0, s - lo) // step)
        b = int(np.ceil((min(hi, s + d) - lo) / step))
        if b > a:
            grid[a:b] = True
    return grid.sum() * step * 1e-9


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_v5e_trace(path):
    """Slices of traces recorded on one TPU v5e in a traced run of each
    cell (the stretch marker cut to the slice)."""
    events = TR.load_events(path)
    red = TR.reduce(events)
    lo, dur = next((e[3], e[4]) for e in events if e[2] == TR.WINDOW_SPAN)
    assert red["window_s"] == pytest.approx(dur * 1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] == pytest.approx(
        _busy_on_a_grid(events, lo, lo + dur), abs=2e-4)
    # per-program device time: module events clipped to the stretch
    want = {}
    for plane, line, name, s, d in events:
        if line == "XLA Modules":
            cut = min(s + d, lo + dur) - max(s, lo)
            if cut > 0:
                key = TR.program_name(name)
                want[key] = want.get(key, 0.0) + cut * 1e-9
    assert {k: v["device_s"] for k, v in red["programs"].items()} == \
        pytest.approx(want)
    assert sum(red["kinds"].values()) == pytest.approx(sum(want.values()))
    assert "decode" in red["kinds"]
    # every idle second is attributed to some host span
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert red["idle_gaps"][0][0].startswith(("engine.", "chipbench."))
