"""Serving telemetry layer: span tracer, metrics registry, and the
profiler annotations that put the engine's spans on the device trace's
clock."""
from __future__ import annotations

import ast
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as MD
from repro.serving import (EngineConfig, ServingEngine, Telemetry,
                           merge_snapshots, validate_trace_events)
from repro.serving.engine import attended_keys
from repro.serving.telemetry import (MetricsRegistry, SpanTracer,
                                     bucket_index, bucket_upper)
from repro.serving.workload import SLO, TenantSpec, make_trace, replay

KEY = jax.random.PRNGKey(9)


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("qwen1.5-0.5b").replace(dtype="float32")
    params = MD.init_params(KEY, cfg)
    return cfg, params


def _tiny_trace(cfg, seed=0):
    tenants = (TenantSpec("t", rate_rps=20.0, prompt_len=(6, 10),
                          new_tokens=(3, 3), priority=0,
                          slo=SLO(ttft_s=float("inf"))),)
    return make_trace(tenants, 0.3, vocab_size=cfg.vocab_size, seed=seed)


def _drive(params, cfg, tel, seed=0, label="engine", **ecfg_kw):
    kw = dict(max_batch=2, max_seq_len=64, max_new_tokens=4)
    kw.update(ecfg_kw)
    eng = ServingEngine(params, cfg, EngineConfig(**kw),
                        telemetry=tel, telemetry_label=label)
    rng = np.random.default_rng(seed)
    for n in (6, 11, 17):
        eng.submit(rng.integers(0, cfg.vocab_size, size=n))
    eng.run()
    return eng


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_virtual_clock_spans_deterministic(setup):
    """Two replays of the same trace must record the identical virtual
    span schedule — names, nesting depth, order, and virtual stamps
    (wall stamps differ run to run; the virtual view must not)."""
    cfg, params = setup
    tr = _tiny_trace(cfg)

    def once():
        tel = Telemetry()
        eng = ServingEngine(params, cfg, EngineConfig(
            max_batch=2, max_seq_len=64, max_new_tokens=4, eos_token=-1),
            telemetry=tel)
        replay(eng, tr, step_quantum_s=0.01)
        return tel.tracer.virtual_schedule()

    a, b = once(), once()
    assert a and a == b
    # replay stamps every span with the virtual clock
    assert all(v0 is not None for (_, _, _, _, _, v0, _) in a)
    # indices are the global start order
    assert [s[0] for s in a] == sorted(s[0] for s in a)


def test_span_nesting_depths(setup):
    """step spans sit at depth 0; admit/retire/dispatch/kv/sample spans
    open inside them at depth >= 1."""
    cfg, params = setup
    tel = Telemetry()
    _drive(params, cfg, tel, seed=1)
    by_name = {}
    for s in tel.tracer.spans:
        by_name.setdefault(s.name, []).append(s)
    assert all(s.depth == 0 for s in by_name["engine.step"])
    for name in ("engine.schedule.admit", "engine.dispatch.prefill",
                 "engine.dispatch.decode", "engine.kv.commit",
                 "engine.sample", "engine.readback"):
        assert name in by_name, f"no {name!r} spans recorded"
        assert all(s.depth >= 1 for s in by_name[name]), name
    # every span closed, wall-ordered within its track
    assert all(s.wall_end_s >= s.wall_start_s for s in tel.tracer.spans)


def test_perfetto_export_schema_valid(setup):
    cfg, params = setup
    tel = Telemetry()
    _drive(params, cfg, tel, seed=2)
    for clock in ("wall", "virtual"):
        obj = tel.tracer.trace_events(clock=clock)
        assert validate_trace_events(obj) == []
        json.dumps(obj)   # artifact must serialize as-is
    names = {e["name"] for e in tel.tracer.trace_events()["traceEvents"]
             if e.get("ph") == "X"}
    assert {"engine.step", "engine.dispatch.prefill",
            "engine.dispatch.decode"} <= names
    with pytest.raises(ValueError, match="clock"):
        tel.tracer.trace_events(clock="lamport")


def test_validate_trace_events_catches_breakage():
    assert validate_trace_events([]) != []
    assert validate_trace_events({"traceEvents": [{"ph": "X"}]}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 0, "tid": 0,
                            "ts": float("nan"), "dur": 1.0}]}
    assert any("ts" in p for p in validate_trace_events(bad))


def test_slowest_spans(setup):
    cfg, params = setup
    tel = Telemetry()
    _drive(params, cfg, tel, seed=3)
    top = tel.tracer.slowest(5)
    assert len(top) == 5
    durs = [s.wall_dur_s for s in top]
    assert durs == sorted(durs, reverse=True)
    assert durs[0] == max(s.wall_dur_s for s in tel.tracer.spans)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_histogram_merge_property():
    """merge of snapshots == snapshot of merged: bucket counts exactly
    (bucketing is a pure per-sample function), sums to float tolerance."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    vals = st.floats(min_value=0.0, max_value=1e6, allow_nan=False,
                     allow_infinity=False)

    @given(xs=st.lists(vals, max_size=40), ys=st.lists(vals, max_size=40))
    @settings(max_examples=60, deadline=None)
    def prop(xs, ys):
        ra, rb, rall = (MetricsRegistry() for _ in range(3))
        for v in xs:
            ra.histogram("h", k="1").observe(v)
            rall.histogram("h", k="1").observe(v)
        for v in ys:
            rb.histogram("h", k="1").observe(v)
            rall.histogram("h", k="1").observe(v)
        merged = merge_snapshots(ra.snapshot(), rb.snapshot())
        whole = rall.snapshot()
        if not xs and not ys:
            assert merged == whole == {}
            return
        mh, wh = merged['h{k="1"}'], whole['h{k="1"}']
        assert mh["counts"] == wh["counts"]
        assert mh["count"] == wh["count"]
        assert mh["sum"] == pytest.approx(wh["sum"])
        assert mh["min"] == wh["min"] and mh["max"] == wh["max"]

    prop()


def test_bucket_index_boundaries():
    assert bucket_index(0.0) == 0
    for i in range(1, 20):
        edge = bucket_upper(i - 1)
        assert bucket_index(edge) == i          # lower edge inclusive
        assert bucket_index(edge * 0.999) == i - 1
    with pytest.raises(ValueError):
        bucket_index(-1e-9)
    with pytest.raises(ValueError):
        bucket_index(float("nan"))


def test_registry_counters_gauges_delta_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("reqs", kind="a").inc()
    reg.counter("reqs", kind="a").inc(2)
    reg.gauge("live").set(3.0)
    reg.histogram("lat").observe(0.5)
    prev = reg.snapshot()
    reg.counter("reqs", kind="a").inc(4)
    reg.gauge("live").set(1.0)
    d = reg.delta(prev)
    assert d['reqs{kind="a"}']["value"] == 4
    assert d["live"]["value"] == 1.0
    text = reg.to_prometheus()
    assert "# TYPE reqs counter" in text
    assert 'reqs{kind="a"} 7' in text
    assert 'le="+Inf"' in text
    assert reg.validate() == []
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("reqs")        # name already registered as a counter
    with pytest.raises(ValueError):
        reg.counter("reqs", kind="a").inc(-1)


# ---------------------------------------------------------------------------
# dispatch spans: every dispatch spanned, with the work it carries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_cache,scheduler", [
    ("contiguous", "blocking"),
    ("paged", "blocking"),
    ("contiguous", "chunked"),
    ("paged", "chunked"),
    ("contiguous", "speculative"),
    ("paged", "speculative"),
])
def test_every_dispatch_has_its_span(setup, kv_cache, scheduler,
                                     monkeypatch):
    """Every ``dispatch_log`` entry has an ``engine.dispatch.<kind>``
    span carrying ``rows``, ``keys`` and ``new_tokens``; a decode
    dispatch's ``keys`` is the sum over its live rows of
    ``min(pos + 1, window)``, counted here from the positions the
    engine holds when it dispatches."""
    cfg, params = setup
    tel = Telemetry()
    want = []
    decode = ServingEngine._decode_step

    def watched(self, live):
        want.append(sum(int(p) + 1 for p in self.slot_pos[live]))
        return decode(self, live)
    monkeypatch.setattr(ServingEngine, "_decode_step", watched)
    eng = _drive(params, cfg, tel, seed=4, label=f"{kv_cache}-{scheduler}",
                 kv_cache=kv_cache, scheduler=scheduler,
                 chunk_tokens=16, spec_gamma=2)
    spans = [s for s in tel.tracer.spans if s.cat == "dispatch"]
    assert [s.name for s in spans] == [
        f"engine.dispatch.{e['kind']}" for e in eng.dispatch_log]
    for s in spans:
        assert {"rows", "keys", "new_tokens"} <= set(s.labels), s.name
        assert s.labels["rows"] >= 1 and s.labels["new_tokens"] >= 1
    got = [s.labels["keys"] for s in spans
           if s.name == "engine.dispatch.decode"]
    assert got == want
    if scheduler == "speculative":
        assert got == [] and any(
            s.name == "engine.dispatch.verify" for s in spans)


def test_attended_keys_by_hand():
    # decode rows at positions 3 and 9: 4 + 10 keys; a window of 5
    # caps the second at 5
    assert attended_keys([3, 9], 1, None) == 14
    assert attended_keys([3, 9], 1, 5) == 9
    # a 4-token chunk after 2 cached positions: 3 + 4 + 5 + 6 keys;
    # under a window of 4: 3 + 4 + 4 + 4
    assert attended_keys([2], 4, None) == 18
    assert attended_keys([2], 4, 4) == 15
    # a row past the window sees exactly the window for every token
    assert attended_keys([10], 3, 4) == 12
    assert attended_keys([], 1, None) == 0


def test_dispatch_never_blocks_on_the_device(setup, monkeypatch):
    """With telemetry on, ``_dispatch`` only enqueues: it never waits
    for the dispatch's result."""
    cfg, params = setup

    def refuse(*a, **k):
        raise AssertionError("block_until_ready on the dispatch path")
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    tel = Telemetry()
    eng = _drive(params, cfg, tel, seed=5, kv_cache="paged",
                 scheduler="chunked", chunk_tokens=16)
    assert len(eng.finished) == 3 and tel.tracer.spans


def test_disabled_telemetry_builds_no_annotation(setup, monkeypatch):
    """Off, no span reaches the profiler: no ``TraceAnnotation`` (or
    step annotation) is built; on, each span builds one."""
    cfg, params = setup
    built = []

    class Counting:
        def __init__(self, name, **kw):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Counting)
    _drive(params, cfg, Telemetry(enabled=False), seed=6)
    _drive(params, cfg, None, seed=6)
    assert built == []
    tel = Telemetry()
    _drive(params, cfg, tel, seed=6)
    assert built == [s.name for s in sorted(tel.tracer.spans,
                                            key=lambda s: s.index)]


# the spans a benchmark harness may write from outside the engine
HARNESS_SPANS = {"engine.admit", "engine.retire", "engine.prefill",
                 "engine.chunk", "engine.decode"}
SERVING = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro" / \
    "serving"


def _span_names(path):
    """Literal names of every ``_span(...)`` call in a module (a name
    built at run time is an f-string: its constant prefix)."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("_span", "span") and node.args):
            continue
        a = node.args[0]
        if isinstance(a, ast.Constant):
            out.add(a.value)
        elif isinstance(a, ast.JoinedStr):
            out.add(a.values[0].value + "*")
        elif isinstance(a, ast.IfExp):
            out |= {a.body.value, a.orelse.value}
    return out


def test_profiler_names_are_unique_and_prefixed(setup):
    """Each profiler name belongs to one kind of span (one category)
    and starts with ``engine.`` (``cluster.`` on the cluster's own
    track); none is a name a harness writes around the engine's
    methods."""
    engine = _span_names(SERVING / "engine.py")
    cluster = _span_names(SERVING / "cluster.py")
    assert engine and all(n.startswith("engine.") for n in engine)
    assert cluster and all(n.startswith("cluster.") for n in cluster)
    assert not (engine | cluster) & HARNESS_SPANS
    cfg, params = setup
    tel = Telemetry()
    for sched in ("blocking", "chunked", "speculative"):
        _drive(params, cfg, tel, seed=7, label=sched, scheduler=sched,
               kv_cache="paged", chunk_tokens=16, spec_gamma=2)
    cats = {}
    for s in tel.tracer.spans:
        assert s.name.startswith("engine.") and s.name not in \
            HARNESS_SPANS, s.name
        cats.setdefault(s.name, set()).add(s.cat)
    assert all(len(c) == 1 for c in cats.values()), cats


@pytest.mark.parametrize("scheduler", ["blocking", "chunked",
                                       "speculative"])
def test_admission_stamp_orders_submit_and_first_token(setup, scheduler):
    """``t_submit <= t_admit <= t_first`` for every finished request,
    telemetry off (the stamp is always taken), with more requests than
    slots so some wait in the queue."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, EngineConfig(
        max_batch=2, max_seq_len=64, max_new_tokens=4,
        scheduler=scheduler, chunk_tokens=16, spec_gamma=2))
    rng = np.random.default_rng(12)
    for n in (6, 11, 17, 9, 13):
        eng.submit(rng.integers(0, cfg.vocab_size, size=n))
    eng.run()
    assert len(eng.finished) == 5
    for r in eng.finished:
        assert r.t_submit <= r.t_admit <= r.t_first, r.rid
    queued = max(r.t_admit - r.t_submit for r in eng.finished)
    assert queued > 0


def test_itl_histogram_observes_every_gap(setup):
    """``engine_itl_s`` gets one sample per gap between consecutive
    tokens of every request, not one mean per request."""
    cfg, params = setup
    tel = Telemetry()
    eng = _drive(params, cfg, tel, seed=13)
    snap = tel.metrics.snapshot()
    h = snap['engine_itl_s{engine="engine",priority="0",tenant="default"}']
    assert h["count"] == sum(len(r.output) - 1 for r in eng.finished)


# ---------------------------------------------------------------------------
# disabled mode
# ---------------------------------------------------------------------------

def test_disabled_telemetry_records_nothing_and_is_bitwise(setup):
    cfg, params = setup
    off = Telemetry(enabled=False)
    eng_off = _drive(params, cfg, off, seed=6, kv_cache="paged")
    assert off.tracer.spans == []
    assert off.metrics.snapshot() == {}
    assert off.engine_aggregates("engine") == {
        "enabled": False, "spans": 0, "span_wall_s": 0.0,
        "dispatches": 0}

    on = Telemetry()
    eng_on = _drive(params, cfg, on, seed=6, kv_cache="paged")
    eng_none = _drive(params, cfg, None, seed=6, kv_cache="paged")
    outs = [{r.rid: r.output for r in e.finished}
            for e in (eng_off, eng_on, eng_none)]
    assert outs[0] == outs[1] == outs[2]
    assert len(on.tracer.spans) > 0


def test_summary_folds_in_telemetry_aggregates(setup):
    cfg, params = setup
    tel = Telemetry()
    eng = _drive(params, cfg, tel, seed=7, label="agg")
    s = eng.summary()["telemetry"]
    assert s["enabled"] and s["spans"] > 0
    assert s["dispatches"] == len(eng.dispatch_log)
    # depth-0 wall time only: no double counting of nested spans
    assert s["span_wall_s"] <= sum(
        sp.wall_dur_s for sp in tel.tracer.spans if sp.tid == "agg") + 1e-9


def test_shared_hub_separates_engine_tracks(setup):
    """One Telemetry across two engines: spans key by label, and each
    engine's aggregates count only its own dispatches."""
    cfg, params = setup
    tel = Telemetry()
    a = _drive(params, cfg, tel, seed=8, label="a")
    b = _drive(params, cfg, tel, seed=9, label="b", kv_cache="paged")
    assert tel.engine_aggregates("a")["dispatches"] == len(a.dispatch_log)
    assert tel.engine_aggregates("b")["dispatches"] == len(b.dispatch_log)
    tids = {s.tid for s in tel.tracer.spans}
    assert {"a", "b"} <= tids


def test_span_tracer_without_engine():
    """The tracer is a standalone zero-dependency primitive."""
    tr = SpanTracer()
    with tr.span("outer", cat="test", tid="t"):
        with tr.span("inner", cat="test", tid="t", detail=1):
            pass
    outer = next(s for s in tr.spans if s.name == "outer")
    inner = next(s for s in tr.spans if s.name == "inner")
    assert outer.depth == 0 and inner.depth == 1
    assert inner.index > outer.index          # start order
    assert inner.labels == {"detail": 1}
    assert outer.wall_dur_s >= inner.wall_dur_s
