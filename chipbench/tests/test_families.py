"""What a family module brings to the harness: its layer scopes, its
needed work and the arguments of its dispatch spans each reach a metric
file with no file of the harness edited. The dense family keeps the
counts of ``work.py`` and ``layer_work.py``; the tiny family of
``tiny_family.py`` brings its own."""
from __future__ import annotations

import importlib.util
import json
import sys

import numpy as np
import pytest

from chipbench import drive, families, run, system
from chipbench import layer_work as LW
from chipbench import spans as SP
from chipbench import trace as TR
from chipbench import work as W
from chipbench.families import dense
from chipbench.tests import tiny, tiny_family

TINY_FAMILY = "tiny_family"
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_s": 1e10}
D = "/device:TPU:0"

# a metric a family with routed experts could add as a file of its own:
# its routed work (from the ``routed`` argument of the decode spans)
# over the device time of its ``moe`` scope in decode programs
MOE_METRIC = '''
def read(rec):
    if rec.spans is None:
        return None
    d = rec.spans["dispatch"].get("decode", {})
    t = rec.spans["device_s"].get("decode", {}).get("moe", 0.0)
    if "routed" not in d or t <= 0:
        return None
    w = rec.needed.decode_work([], d)
    return 100.0 * w.least_seconds(rec.peaks["bf16_flops"],
                                   rec.peaks["hbm_bytes_s"]) / t
'''


@pytest.fixture(autouse=True)
def tiny_family_in_the_package(monkeypatch):
    """The tiny family stands where a new family's file would be,
    ``chipbench/families/<name>.py``, without a file written there."""
    monkeypatch.setitem(sys.modules, f"chipbench.families.{TINY_FAMILY}",
                        tiny_family)


def _read_file(path):
    """``read(record)`` of a metric file outside the harness's own."""
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_a_family_is_found_by_its_name():
    assert families.load({"family": "dense"}) is dense
    assert families.load({"family": TINY_FAMILY}) is tiny_family


def test_the_dense_family_keeps_the_dense_counts_and_scopes():
    c = json.loads((run.HERE / "configs" / "phi3-mini-3.8b.json")
                   .read_text())
    nd = families.Needed(families.load(c), c)
    s = W.Shapes.from_config(c)
    assert nd.shapes == s
    assert nd.scopes == ("attention", "kv_write", "mlp", "logits",
                         "attn_proj")
    assert nd.decode_work([100, 10], {"rows": 2}) == W.decode(s, [100, 10])
    assert nd.prefill_work(256, 512, {}) == W.prefill(s, 256, 512)
    assert nd.decode_attention_work(1000, 5) == \
        LW.decode_attention(s, 1000, 5)
    assert nd.kv_write_work(256) == LW.kv_write(s, 256)


def _trace():
    """A stretch of 1000 ns: one decode dispatch whose span carries a
    ``routed`` argument, and a decode program with operations in the
    ``attention`` and ``moe`` scopes and one in ``mlp``, which the tiny
    family does not name."""
    def op(name, start, dur, path):
        return [D, "XLA Ops", name, float(start), float(dur), {"op": path}]
    return [
        ["/host:CPU", "host", TR.WINDOW_SPAN, 0.0, 1000.0, {}],
        ["/host:CPU", "host", "engine.dispatch.decode", 100.0, 10.0,
         {"rows": 2, "keys": 30, "new_tokens": 2, "routed": 7, "rid": 4}],
        [D, "XLA Modules", "jit_decode(3)", 200.0, 400.0, {}],
        op("fusion.1", 200, 100, "jit(decode)/attention/dot_general"),
        op("fusion.2", 300, 200, "jit(decode)/moe/dot_general"),
        op("fusion.3", 500, 100, "jit(decode)/mlp/dot_general"),
    ]


def test_scope_argument_and_work_of_a_family_reach_metric_files(tmp_path):
    c = dict(tiny.config(), family=TINY_FAMILY)
    nd = families.Needed(families.load(c), c)
    red = SP.reduce(_trace(), nd.scopes)
    assert red["device_s"]["decode"] == pytest.approx(
        {"attention": 100e-9, "moe": 200e-9, "unscoped": 100e-9})
    assert red["dispatch"]["decode"] == {"n": 1, "rows": 2, "keys": 30,
                                         "new_tokens": 2, "routed": 7}
    rec = drive.Record(sent=[], steps=[], w0=0.0, w1=1.0, spans=red,
                       needed=nd, peaks=PEAKS)
    # the family's attention work in the harness's own metric file:
    # 100 B x 2 layers x (30 - 2) keys at 1e10 B/s = 560 ns over 100 ns
    assert run.metric_reader("decode_attention_roofline")(rec) == \
        pytest.approx(560.0)
    # its routed work in a metric file of its own: 1000 x 2 layers x 7
    # routed tokens at 1e12 operations/s = 14 ns over 200 ns of moe
    (tmp_path / "moe_roofline.py").write_text(MOE_METRIC)
    assert _read_file(tmp_path / "moe_roofline.py")(rec) == \
        pytest.approx(7.0)


def test_watch_prices_each_dispatch_with_the_family_and_its_span():
    """A tiny engine with the program's telemetry on, watched: every
    dispatch's record is the family's work of that dispatch's own span
    arguments, and their sum reaches ``mfu``."""
    c = dict(tiny.config(kind="contig"), family=TINY_FAMILY)
    fam = families.load(c)
    nd = families.Needed(fam, c)
    eng = system.make_engine(
        fam.program_params(fam.make_weights(c, 3, dtype=c["torch_dtype"])),
        c, tiny.base_arch(c), telemetry=True)
    watch = system.Watch(eng, nd)
    assert watch.enabled
    rng = np.random.default_rng(0)
    for n in (9, 17, 30):
        eng.submit(rng.integers(0, 256, n, dtype=np.int32),
                   max_new_tokens=5)
    eng.run()
    keys = [s.labels["keys"] for s in eng.telemetry.tracer.spans
            if s.name == "engine.dispatch.decode"]
    got = [w.flops / (tiny_family.EXPERT_FLOPS * 2)
           for _, kind, w in watch.records if kind == "decode"]
    assert keys and got == keys
    assert [w.flops for _, kind, w in watch.records if kind == "prefill"] \
        == [tiny_family.EXPERT_FLOPS * 2 * n for n in (9, 17, 30)]
    total = W.Work()
    for _, _, w in watch.records:
        total += w
    rec = drive.Record(sent=[], steps=[], w0=0.0, w1=1.0, peaks=PEAKS,
                       trace={"window_s": 2.0},
                       stretch_work={"decode": total})
    assert run.metric_reader("mfu")(rec) == pytest.approx(
        100.0 * total.flops / (2.0 * PEAKS["bf16_flops"]))


def test_watch_prices_a_prefill_by_its_own_span_beside_a_draft():
    """With a speculative draft, an admission dispatches the prefill and
    then the draft's prefill: the prefill is priced with the arguments
    of its own span, not the draft's (here made to differ)."""
    c = dict(tiny.config(kind="contig"), family=TINY_FAMILY)
    c["engine"] = dict(c["engine"], scheduler="speculative", draft="self")
    fam = families.load(c)
    eng = system.make_engine(
        fam.program_params(fam.make_weights(c, 3, dtype=c["torch_dtype"])),
        c, tiny.base_arch(c), telemetry=True)
    watch = system.Watch(eng, families.Needed(fam, c))
    assert watch.enabled
    watched = eng._dispatch

    def draft_apart(kind, fn, params, *a, **span_args):
        if kind == "draft_prefill":
            span_args["routed"] = 10**6
        return watched(kind, fn, params, *a, **span_args)
    eng._dispatch = draft_apart
    rng = np.random.default_rng(1)
    for n in (9, 17, 30):
        eng.submit(rng.integers(0, 256, n, dtype=np.int32),
                   max_new_tokens=5)
    eng.run()
    assert eng.draft_dispatches >= 3
    assert [w.flops for _, kind, w in watch.records if kind == "prefill"] \
        == [tiny_family.EXPERT_FLOPS * 2 * n for n in (9, 17, 30)]
