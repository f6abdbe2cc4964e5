"""Serving telemetry layer: span tracer, metrics registry, and the
measured-vs-predicted dispatch profiler (the loop-closer on the jaxpr
cost model)."""
from __future__ import annotations

import json
import math

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as MD
from repro.serving import (EngineConfig, ServingEngine, Telemetry,
                           dispatch_calibration, join_coverage,
                           merge_snapshots, validate_trace_events)
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
    assert all(s.depth == 0 for s in by_name["step"])
    for name in ("admit", "prefill", "decode", "kv_commit", "sample"):
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
    assert {"step", "prefill", "decode"} <= names
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
# dispatch profiler: 100% join + finite calibration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_cache,scheduler", [
    ("contiguous", "blocking"),
    ("paged", "blocking"),
    ("contiguous", "chunked"),
    ("paged", "chunked"),
    ("contiguous", "speculative"),
    ("paged", "speculative"),
])
def test_profiler_joins_every_dispatch(setup, kv_cache, scheduler):
    """Every dispatch_log entry gets a measured wall-time sample, every
    logged kind gets a span, and the calibration joining both against
    the traced FLOPs/bytes is finite for every kind."""
    cfg, params = setup
    tel = Telemetry()
    eng = _drive(params, cfg, tel, seed=4, label=f"{kv_cache}-{scheduler}",
                 kv_cache=kv_cache, scheduler=scheduler,
                 chunk_tokens=16, spec_gamma=2)
    joined, total = join_coverage(eng, tel)
    assert total > 0 and joined == total
    logged = {e["kind"] for e in eng.dispatch_log}
    spanned = {s.name for s in tel.tracer.spans if s.cat == "dispatch"}
    assert logged <= spanned
    calib = dispatch_calibration(eng, tel)
    assert set(calib) == logged
    for kind, row in calib.items():
        assert row["n"] >= 1, kind
        assert row["predicted_s"] > 0, kind
        assert math.isfinite(row["model_error_ratio"]), kind
        assert row["achieved_flops_per_s"] >= 0, kind


def test_calibration_respects_hardware_profile(setup, monkeypatch):
    """predicted_s scales with the profile roofline: a faster profile
    predicts less time, so the measured/predicted ratio grows. On a TPU
    the host reference peaks are refused: a profile must be given."""
    from repro.core import profiles as HW
    cfg, params = setup
    tel = Telemetry()
    eng = _drive(params, cfg, tel, seed=5)
    host = dispatch_calibration(eng, tel)
    pim = dispatch_calibration(eng, tel, profile=HW.PIM_AI_CHIP)
    for kind in host:
        assert pim[kind]["predicted_s"] != host[kind]["predicted_s"]
        assert math.isfinite(pim[kind]["model_error_ratio"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="explicit profile"):
        dispatch_calibration(eng, tel)
    assert dispatch_calibration(eng, tel, profile=HW.PIM_AI_CHIP) == pim


# ---------------------------------------------------------------------------
# disabled mode
# ---------------------------------------------------------------------------

def test_disabled_telemetry_records_nothing_and_is_bitwise(setup):
    cfg, params = setup
    off = Telemetry(enabled=False)
    eng_off = _drive(params, cfg, off, seed=6, kv_cache="paged")
    assert off.tracer.spans == []
    assert off.metrics.snapshot() == {}
    assert off.profiler.samples == []
    assert off.engine_aggregates("engine") == {
        "enabled": False, "spans": 0, "span_wall_s": 0.0,
        "dispatches": 0, "dispatch_wall_s": 0.0}

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
    assert s["dispatch_wall_s"] > 0
    # depth-0 wall time only: no double counting of nested spans
    assert s["span_wall_s"] <= sum(
        sp.wall_dur_s for sp in tel.tracer.spans if sp.tid == "agg") + 1e-9


def test_shared_hub_separates_engine_tracks(setup):
    """One Telemetry across two engines: spans/samples key by label, and
    join/calibration only consume the matching engine's samples."""
    cfg, params = setup
    tel = Telemetry()
    a = _drive(params, cfg, tel, seed=8, label="a")
    b = _drive(params, cfg, tel, seed=9, label="b", kv_cache="paged")
    assert join_coverage(a, tel) == (len(a.dispatch_log),
                                     len(a.dispatch_log))
    assert join_coverage(b, tel) == (len(b.dispatch_log),
                                     len(b.dispatch_log))
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
