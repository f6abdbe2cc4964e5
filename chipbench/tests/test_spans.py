"""The per-layer reduction from the serving program's own spans and layer
scopes (``spans.py``), the single-layer work it is priced with
(``layer_work.py``), the queue-wait reader, and the recorded v5e
slices: the new ones with the program's spans and scopes, and the
earlier ones, which ``trace.reduce`` must still reduce exactly as it
did."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import drive, families
from chipbench import layer_work as LW
from chipbench import run
from chipbench import spans as SP
from chipbench import trace as TR
from chipbench import work as W
from chipbench.families import dense

SCOPES = dense.SCOPES

D = "/device:TPU:0"


def _host(name, start, dur, **args):
    return ["/host:CPU", "host", name, float(start), float(dur), args]


def _mod(name, start, dur):
    return [D, "XLA Modules", name, float(start), float(dur), {}]


def _op(name, start, dur, path=None):
    return [D, "XLA Ops", name, float(start), float(dur),
            {} if path is None else {"op": path}]


def _synthetic():
    # stretch 1000..3000 ns: two engine steps, three dispatches in it
    # and one before it, decode/prefill/splice programs with scoped ops
    return [
        _host(TR.WINDOW_SPAN, 1000, 2000),
        _host("engine.dispatch.decode", 900, 50, rows=9, keys=99,
              new_tokens=9),
        _host("engine.step", 1000, 800, step_num=1),
        _host("engine.dispatch.decode", 1100, 50, rows=2, keys=30,
              new_tokens=2),
        _host("engine.dispatch.prefill", 1200, 50, rows=1, keys=10,
              new_tokens=4, rid=3),
        _host("engine.readback", 1500, 200),
        _host("engine.step", 2000, 900, step_num=2),
        _host("engine.dispatch.decode", 2100, 50, rows=2, keys=32,
              new_tokens=2),
        _host("engine.readback", 2500, 300),
        _host("engine.step", 2950, 100, step_num=3),    # ends after
        _mod("jit_prefill(4)", 1200, 100),
        _op("fusion.7", 1200, 60, "jit(prefill)/while/body/attention/dot"),
        _op("fusion.8", 1260, 40, "jit(prefill)/logits/dot_general"),
        _mod("jit_decode(12)", 1300, 200),
        _op("while.1", 1300, 200, "jit(decode)/while"),
        _op("fusion.1", 1300, 100,
            "jit(decode)/while/body/attention/dot_general"),
        _op("fusion.2", 1400, 50, "jit(decode)/while/body/mlp/dot_general"),
        _op("copy.3", 1450, 50),
        _mod("jit_splice_rows(2)", 1700, 50),
        _op("dynamic-update-slice", 1700, 50,
            "jit(splice_rows)/kv_write/dynamic_update_slice"),
        _mod("jit_decode(12)", 2300, 200),
        _op("fusion.1", 2300, 120,
            "jit(decode)/while/body/attention/dot_general"),
        _op("fusion.5", 2420, 80,
            "jit(decode)/kv_write/kv_write/dynamic_update_slice"),
    ]


TINY = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
        "vocab_size": 10, "torch_dtype": "bfloat16", "family": "dense"}
NEEDED = families.Needed(dense, TINY)
SHAPES = NEEDED.shapes
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_s": 1e10}


def test_scope_is_the_innermost_layer_on_the_path():
    assert SP.scope_of("jit(decode)/while/body/attention/dot", SCOPES) == \
        "attention"
    assert SP.scope_of("jit(f)/mlp/attention/x", SCOPES) == "attention"
    assert SP.scope_of("jit(decode)/jit(decode_attention)/x", SCOPES) == \
        SP.UNSCOPED
    assert SP.scope_of("jit(f)/mlp/attention:", SCOPES) == "attention"
    assert SP.scope_of("", SCOPES) == SP.scope_of(None, SCOPES) == \
        SP.UNSCOPED
    # a family that names no scopes leaves every operation unscoped
    assert SP.scope_of("jit(f)/mlp/attention/x", ()) == SP.UNSCOPED


def test_reduce_by_kind_and_scope_dispatch_work_and_host_self_time():
    red = SP.reduce(_synthetic(), SCOPES)
    assert red["window_s"] == pytest.approx(2000e-9)
    dev = red["device_s"]
    # the while loop's own span is left out; copy.3 has no scope
    assert dev["decode"] == pytest.approx(
        {"attention": 220e-9, "mlp": 50e-9, "unscoped": 50e-9,
         "kv_write": 80e-9})
    assert dev["prefill"] == pytest.approx({"attention": 60e-9,
                                            "logits": 40e-9})
    assert dev["kv_update"] == pytest.approx({"kv_write": 50e-9})
    # the dispatch at 900 ns was enqueued before the stretch
    assert red["dispatch"] == {
        "decode": {"n": 2, "rows": 4, "keys": 62, "new_tokens": 4},
        "prefill": {"n": 1, "rows": 1, "keys": 10, "new_tokens": 4}}
    # every span that started in the stretch, by name
    assert red["host_s"]["engine.step"] == pytest.approx(
        [800e-9, 900e-9, 100e-9])
    assert red["host_s"]["engine.readback"] == pytest.approx(
        [200e-9, 300e-9])
    assert TR.WINDOW_SPAN not in red["host_s"]
    # two whole steps: (800 - 200) and (900 - 300) ns outside readbacks
    assert red["steps"] == 2
    assert red["host_self_s"] == pytest.approx(600e-9)
    assert SP.unscoped_share(red, "decode") == pytest.approx(
        100 * 50 / 400)


def test_metrics_from_the_reduction_by_hand():
    red = SP.reduce(_synthetic(), SCOPES)
    # decode attention: 62 keys over 4 rows, 32 bytes of KV a position
    # (2 layers x K and V x 1 head x 4 x 2 bytes): 58 x 32 = 1856 bytes
    # at 1e10 B/s = 185.6 ns (the 3968 operations take 3.968 ns), over
    # 220 ns of attention in decode programs
    assert SP.decode_attention_roofline(red, NEEDED, PEAKS) == \
        pytest.approx(100 * 185.6 / 220)
    # 8 positions written x 32 bytes = 25.6 ns over 80 + 50 ns
    assert SP.kv_write_roofline(red, NEEDED, PEAKS) == pytest.approx(
        100 * 25.6 / 130)
    assert SP.host_self_ms(red) == pytest.approx(600e-6)


def test_metrics_report_nothing_without_spans_or_scopes():
    """The reduction of a program with no spans and no scopes: the
    metrics find nothing to read and say so, and raise nothing."""
    bare = [e[:5] + [{}] for e in _synthetic()
            if e[1] != "host" or e[2] == TR.WINDOW_SPAN]
    red = SP.reduce(bare, SCOPES)
    assert red["dispatch"] == {} and red["steps"] == 0
    assert red["device_s"]["decode"] == pytest.approx(
        {"unscoped": 400e-9})
    assert SP.decode_attention_roofline(red, NEEDED, PEAKS) is None
    assert SP.kv_write_roofline(red, NEEDED, PEAKS) is None
    assert SP.host_self_ms(red) is None


def test_missing_stretch_or_device_is_an_error():
    with pytest.raises(ValueError):
        SP.reduce([e for e in _synthetic() if e[2] != TR.WINDOW_SPAN])
    with pytest.raises(ValueError):
        SP.reduce([e for e in _synthetic() if e[1] == "host"])


def test_layer_work_by_hand():
    """phi3-mini-3.8b: 32 layers, 32 KV heads of 96, bf16 (393,216 bytes
    of KV a position); h2o-danube-1.8b: 24 layers, 8 KV heads of 80
    (61,440 bytes)."""
    cfg = json.loads((run.HERE / "configs" / "phi3-mini-3.8b.json")
                     .read_text())
    phi3 = W.Shapes.from_config(cfg)
    assert phi3.kv_bytes_per_position == 32 * 2 * 32 * 96 * 2 == 393216
    w = LW.decode_attention(phi3, keys=1000, rows=5)
    assert w.flops == 4 * 32 * 32 * 96 * 1000 == 393216000
    assert w.bytes == 995 * 393216
    assert LW.kv_write(phi3, 256).bytes == 256 * 393216
    assert LW.kv_write(phi3, 256).flops == 0
    cfg = json.loads((run.HERE / "configs" / "h2o-danube-1.8b.json")
                     .read_text())
    danube = W.Shapes.from_config(cfg)
    assert danube.kv_bytes_per_position == 24 * 2 * 8 * 80 * 2 == 61440
    # 64 rows at position 829 each: 830 keys a row
    w = LW.decode_attention(danube, keys=64 * 830, rows=64)
    assert w.flops == 4 * 24 * 32 * 80 * 64 * 830
    assert w.bytes == 64 * 829 * 61440
    # the decode dispatch's own count of the same history agrees
    whole = W.decode(danube, [829] * 64)
    assert whole.bytes - w.bytes == danube.weight_bytes + \
        64 * danube.d * 2 + 64 * danube.kv_bytes_per_position


def _sent(due, t_submit, t_admit=None):
    req = SimpleNamespace(t_submit=t_submit)
    if t_admit is not None:
        req.t_admit = t_admit
    return drive.Sent(index=0, due=due, submit=due, n_prompt=1, n_out=1,
                      req=req)


def test_queue_wait_reader():
    read = run.metric_reader("queue_wait_p95_ms")
    sent = [_sent(1.0 + i, 100.0 + i, 100.0 + i + 0.001 * i)
            for i in range(5)]
    sent.append(_sent(2.5, 200.0, 0.0))         # not admitted yet
    sent.append(_sent(9.0, 300.0, 301.0))       # due after the window
    rec = drive.Record(sent=sent, steps=[], w0=1.0, w1=8.0)
    # waits 0, 1, 2, 3, 4 ms: numpy's p95 interpolates to 3.8
    assert read(rec) == pytest.approx(3.8)
    # a program that takes no admission stamp: nothing to read
    rec = drive.Record(sent=[_sent(1.0, 100.0)], steps=[], w0=0.0, w1=2.0)
    assert read(rec) is None


DATA = Path(__file__).resolve().parent / "data"
EARLIER = sorted(DATA.glob("*.v5e.json.gz"))


@pytest.mark.parametrize("path", EARLIER, ids=[p.name for p in EARLIER])
def test_earlier_slices_reduce_as_before(path):
    """``trace.reduce`` of the slices recorded before the program had
    spans or scopes, to the last digit it gave then."""
    want = json.loads(Path(str(path).replace(".v5e.json.gz",
                                             ".v5e.reduced.json"))
                      .read_text())
    assert json.loads(json.dumps(TR.reduce(TR.load_events(path)))) == want


def test_extract_reads_spans_and_their_arguments(tmp_path):
    """A profiler trace taken here: the host spans keep their integer
    arguments."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 64))
    jax.jit(jnp.tanh)(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        with jax.profiler.StepTraceAnnotation(SP.STEP_SPAN, step_num=3):
            with jax.profiler.TraceAnnotation(
                    "engine.dispatch.decode", rows=2, keys=9, new_tokens=2):
                jax.jit(jnp.tanh)(x).block_until_ready()
    jax.profiler.stop_trace()
    host = {e[2]: e[5] for e in SP.extract(TR.find_xplane(tmp_path))
            if e[1] == "host"}
    assert host["engine.dispatch.decode"] == {"rows": 2, "keys": 9,
                                              "new_tokens": 2}
    assert host[SP.STEP_SPAN] == {"step_num": 3}
    assert TR.WINDOW_SPAN in host


def test_op_names_of_a_recorded_v5e_trace():
    """A trace recorded on one TPU v5e of a jitted function with an
    ``attention`` and an ``mlp`` scope, three calls: the device keeps
    each operation's ``op_name`` (as ``path:``) in its metadata."""
    path = DATA / "scopes.v5e.xplane.pb"
    names = SP.op_names(path.read_bytes())
    assert {SP.scope_of(p, SCOPES) for p in names.values()} == \
        {"attention"}
    ops = [e for e in SP.extract(path) if e[1] == "XLA Ops"]
    assert len(ops) == 3
    assert all(e[5]["op"] == "jit(layer)/attention/dot_general:"
               for e in ops)


# hand-checked on the recorded slices (four engine steps of each cell's
# traced run on one TPU v5e with the program's telemetry on): dispatch
# sums from the ``engine.dispatch.*`` arguments, host self time from
# the ``engine.step`` and ``engine.readback`` spans, device time by
# scope from the operations' program and ``op_name``
RECORDED_SPANS = {
    "danube-decode-contig": dict(
        config="h2o-danube-1.8b", window_s=0.151074708,
        dispatch={"decode": {"n": 4, "rows": 256, "keys": 216540,
                             "new_tokens": 256}},
        self_ns=[5736340, 5889400, 5372089, 5921930],
        decode={"attention": 56.24967e-3, "kv_write": 40.226042e-3,
                "mlp": 13.906983e-3, "attn_proj": 3.327841e-3,
                "logits": 0.927994e-3, "unscoped": 13.342595e-3},
        # (216540 - 256) keys x 61,440 B at 819 GB/s = 16.2253 ms over
        # 56.2497 ms; 256 x 61,440 B = 19.2047 us over 40.2260 ms
        attention_roofline=28.845078, kv_write_roofline=0.04774193),
    "phi3-chat-paged": dict(
        config="phi3-mini-3.8b", window_s=0.511763343,
        dispatch={"decode": {"n": 4, "rows": 16, "keys": 13920,
                             "new_tokens": 16}},
        self_ns=[5049349, 4469189, 5531520, 5500000],
        decode={"attention": 160.812261e-3, "kv_write": 23.258587e-3,
                "mlp": 26.081482e-3, "attn_proj": 8.559065e-3,
                "logits": 1.054734e-3, "unscoped": 272.000227e-3},
        # (13920 - 16) x 393,216 B = 6.6727 ms over 160.8123 ms;
        # 16 x 393,216 B = 7.6818 us over 23.2586 ms
        attention_roofline=4.1511448, kv_write_roofline=0.03302813),
}


@pytest.mark.parametrize("cell", sorted(RECORDED_SPANS))
def test_recorded_v5e_slice_with_program_spans(cell):
    want = RECORDED_SPANS[cell]
    events = TR.load_events(DATA / f"{cell}.v5e.spans.json.gz")
    red = SP.reduce(events, SCOPES)
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["dispatch"] == want["dispatch"]
    assert red["steps"] == len(want["self_ns"])
    assert red["host_self_s"] == pytest.approx(
        sum(want["self_ns"]) / len(want["self_ns"]) * 1e-9)
    assert red["device_s"]["decode"] == pytest.approx(want["decode"])
    cfg = json.loads((run.HERE / "configs" / f"{want['config']}.json")
                     .read_text())
    needed, peaks = families.Needed(families.load(cfg), cfg), {
        "bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    assert SP.decode_attention_roofline(red, needed, peaks) == \
        pytest.approx(want["attention_roofline"])
    assert SP.kv_write_roofline(red, needed, peaks) == \
        pytest.approx(want["kv_write_roofline"])
    share = 100 * want["decode"]["unscoped"] / sum(want["decode"].values())
    assert SP.unscoped_share(red, "decode") == pytest.approx(share)
    # the metric files read the same from a traced run's record, and the
    # busy union reads the six-field events as it read five
    rec = drive.Record(sent=[], steps=[], w0=0.0, w1=1.0, spans=red,
                       needed=needed, peaks=peaks,
                       trace=TR.reduce(events))
    assert rec.trace == TR.reduce([e[:5] for e in events])
    assert run.metric_reader("decode_attention_roofline")(rec) == \
        pytest.approx(want["attention_roofline"])
    assert run.metric_reader("kv_write_roofline")(rec) == \
        pytest.approx(want["kv_write_roofline"])
    assert run.metric_reader("host_self_ms")(rec) == pytest.approx(
        sum(want["self_ns"]) / len(want["self_ns"]) * 1e-6)
    assert run.metric_reader("kv_handoff_p95_ms")(rec) is None
