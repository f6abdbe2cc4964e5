"""The benchmark's arithmetic, on the CPU: metric readers, the seeded
traffic generator, the peaks table and the work counts."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import loadgen, peaks, run, work
from chipbench.drive import Record, Sent, Step


def _sent(i, due, submit, stamps, n_out=None):
    return Sent(index=i, due=due, submit=submit, n_prompt=10,
                n_out=n_out or len(stamps), req=None, stamps=list(stamps))


def _record():
    # window [10, 20): request 0 due before it, 1 and 2 inside, 3 after
    sent = [_sent(0, 9.0, 9.0, [9.5, 10.5, 11.0]),
            _sent(1, 10.0, 10.25, [11.0, 11.5, 13.0, 20.5]),
            _sent(2, 12.0, 12.5, [12.5, 19.0]),
            _sent(3, 21.0, 21.0, [21.5])]
    steps = [Step(9.8, 10.5, 1), Step(10.5, 11.0, 1), Step(11.0, 11.5, 0),
             Step(19.5, 20.5, 1)]
    return Record(sent=sent, steps=steps, w0=10.0, w1=20.0, setup_s=3.5)


def read(name, rec):
    return run.metric_reader(name)(rec)


def test_ttft_p95_is_over_requests_due_in_the_window_from_due_time():
    rec = _record()
    # requests 1 and 2: 11.0 - 10.0 = 1.0 and 12.5 - 12.0 = 0.5
    assert read("ttft_p95_s", rec) == pytest.approx(
        np.percentile([1.0, 0.5], 95))


def test_itl_p95_takes_every_gap_ending_in_the_window():
    rec = _record()
    # gaps ending in [10, 20): 1.0 (req 0), 0.5 (req 0), 0.5, 1.5 (req 1),
    # 6.5 (req 2); req 1's gap ending at 20.5 and req 3 are outside
    gaps = [1.0, 0.5, 0.5, 1.5, 6.5]
    assert read("itl_p95_ms", rec) == pytest.approx(
        1e3 * np.percentile(gaps, 95))


def test_output_rate_counts_tokens_stamped_in_the_window():
    # 10.5, 11.0 | 11.0, 11.5, 13.0 | 12.5, 19.0 -> 7 tokens over 10 s
    assert read("output_tok_s", _record()) == pytest.approx(0.7)


def test_loadgen_lag_is_submit_minus_due():
    assert read("loadgen_lag_p99_ms", _record()) == pytest.approx(
        1e3 * np.percentile([0.25, 0.5], 99))


def test_step_metrics_use_steps_ending_in_the_window():
    rec = _record()
    assert read("step_ms", rec) == pytest.approx(1e3 * (0.7 + 0.5 + 0.5)
                                                 / 3)
    assert read("decode_batch_mean", rec) == pytest.approx(1.0)
    assert read("setup_s", rec) == 3.5


def test_trace_metrics_report_nothing_without_a_trace():
    rec = _record()
    for name in ("decode_roofline", "chunk_roofline", "prefill_roofline",
                 "mfu", "device_idle_share"):
        assert read(name, rec) is None


def test_roofline_and_mfu_from_reduced_trace_and_work():
    rec = _record()
    rec.peaks = peaks.peaks_for("TPU v5 lite")
    rec.trace = {"window_s": 2.0, "busy_s": 1.5,
                 "kinds": {"decode": 0.5, "chunk_paged": 0.25}}
    rec.stretch_work = {"decode": work.Work(197e12 * 0.1, 819e9 * 0.2),
                        "chunk_paged": work.Work(197e12 * 0.2, 0.0)}
    assert read("decode_roofline", rec) == pytest.approx(40.0)
    assert read("chunk_roofline", rec) == pytest.approx(80.0)
    assert read("prefill_roofline", rec) is None      # no prefill dispatch
    assert read("mfu", rec) == pytest.approx(100 * 0.3 / 2.0)
    assert read("device_idle_share", rec) == pytest.approx(25.0)


MIX = {"loop": "open", "rate_rps": 4.0, "ramp_s": 2.0, "drain_cap_s": 5.0,
       "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                  "min": 64, "max": 1536, "quantum": 1},
       "output": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                  "min": 32, "max": 512, "quantum": 1}}


def test_same_seed_same_schedule_and_lengths():
    a = loadgen.make_plan(MIX, 2**33 + 1, 20.0, 32064, 2048)
    b = loadgen.make_plan(MIX, 2**33 + 1, 20.0, 32064, 2048)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.n_out, b.n_out)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def test_another_seed_reorders_the_same_work():
    a = loadgen.make_plan(MIX, 1, 20.0, 32064, 2048)
    b = loadgen.make_plan(MIX, 2, 20.0, 32064, 2048)
    assert not np.array_equal(a.n_out, b.n_out)

    def window(p):      # the requests due in [ramp, ramp + seconds)
        w = (p.due >= 2.0) & (p.due < 22.0)
        return (sorted(p.n_out[w]), sorted(len(x) for x, y in
                                           zip(p.prompts, w) if y))
    assert len(window(a)[0]) == round(4.0 * 20.0)
    assert window(a) == window(b)
    assert a.due[0] >= 0.0 and np.all(np.diff(a.due) >= 0)


def test_closed_loop_lengths_are_stratified():
    mix = dict(MIX, loop="closed", clients=8)
    a = loadgen.make_plan(mix, 1, 10.0, 1000, 2048)
    b = loadgen.make_plan(mix, 2, 10.0, 1000, 2048)
    k = 32 * (len(a.n_out) // 32)
    assert sorted(a.n_out[:k]) == sorted(b.n_out[:k])
    assert not np.array_equal(a.n_out, b.n_out)


def test_lengths_are_clipped_and_quantized():
    spec = {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 64,
            "max": 512, "quantum": 64}
    x = loadgen.lengths(spec, 640, np.random.default_rng(0), 32)
    assert x.min() >= 64 and x.max() <= 512 and not (x % 64).any()
    assert set(x) <= set(run.warm_lengths(spec))


def test_closed_loop_residual_start_and_sessions():
    mix = dict(MIX, loop="closed", clients=8, residual_start=True,
               sessions={"groups": 2, "prefix": {"dist": "fixed",
                                                 "value": 40}})
    p = loadgen.make_plan(mix, 5, 10.0, 1000, 2048)
    assert p.clients == 8 and p.due is None
    assert p.n_out[:8].min() >= 2
    assert np.array_equal(p.prompts[0][:40], p.prompts[2][:40])
    assert not np.array_equal(p.prompts[0][:40], p.prompts[1][:40])


def test_bursts_add_requests_due_together():
    p = loadgen.make_plan(dict(MIX, bursts={"every_s": 5.0, "size": 3}),
                          1, 20.0, 1000, 2048)
    q = loadgen.make_plan(MIX, 1, 20.0, 1000, 2048)
    assert len(p.prompts) > len(q.prompts)
    assert np.sum(np.isclose(p.due, 5.0)) >= 3


def test_warm_lengths_cover_power_of_two_buckets():
    spec = MIX["prompt"]
    assert run.warm_lengths(spec) == [64, 128, 256, 512, 1024, 1536]


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


PHI3 = {"hidden_size": 3072, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "intermediate_size": 8192, "vocab_size": 32064,
        "sliding_window": 2047, "torch_dtype": "bfloat16"}
DANUBE = {"hidden_size": 2560, "num_hidden_layers": 24,
          "num_attention_heads": 32, "num_key_value_heads": 8,
          "intermediate_size": 6912, "vocab_size": 32000,
          "sliding_window": 4096, "torch_dtype": "bfloat16"}


def test_work_matches_hand_counts_phi3():
    s = work.Shapes.from_config(PHI3)
    # per layer: q,k,v,o 4 * 3072^2 + SwiGLU 3 * 3072 * 8192
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert s.layer_matmul_params == per_layer == 113_246_208
    weights = 2 * (32 * (per_layer + 2 * 3072) + 3072 + 32064 * 3072)
    assert s.weight_bytes == weights == 7_445_157_888
    assert s.kv_bytes_per_position == 32 * 2 * 32 * 96 * 2 == 393_216
    # decode: two rows at positions 100 and 10
    w = work.decode(s, [100, 10])
    attn = 4 * 32 * 32 * 96 * (101 + 11)
    assert w.flops == 2 * (2 * 32 * per_layer + 2 * 32064 * 3072) + attn
    assert w.bytes == weights + 2 * 3072 * 2 + (100 + 10) * 393_216 \
        + 2 * 393_216
    # a 256-token chunk over 512 cached positions
    c = work.prefill(s, 256, 512)
    keys = sum(512 + j + 1 for j in range(256))
    assert c.flops == (256 * 2 * 32 * per_layer + 2 * 32064 * 3072
                       + 4 * 32 * 32 * 96 * keys)
    assert c.bytes == weights + 256 * 3072 * 2 + 512 * 393_216 \
        + 256 * 393_216


def test_work_matches_hand_counts_danube():
    s = work.Shapes.from_config(DANUBE)
    # q,o 2 * 2560 * 2560; k,v 2 * 2560 * 640; SwiGLU 3 * 2560 * 6912
    per_layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert s.layer_matmul_params == per_layer == 69_468_160
    assert s.kv_bytes_per_position == 24 * 2 * 8 * 80 * 2 == 61_440
    weights = 2 * (24 * (per_layer + 2 * 2560) + 2560 + 32000 * 2560)
    assert s.weight_bytes == weights
    p = work.prefill(s, 192)
    keys = 192 * 193 // 2
    assert p.flops == (192 * 2 * 24 * per_layer + 2 * 32000 * 2560
                       + 4 * 24 * 32 * 80 * keys)
    assert p.bytes == weights + 192 * 2560 * 2 + 192 * 61_440
    # past the 4096 window a row attends 4096 keys, reads 4095 cached
    d = work.decode(s, [5000])
    assert d.flops == (2 * 24 * per_layer + 2 * 32000 * 2560
                       + 4 * 24 * 32 * 80 * 4096)
    assert d.bytes == weights + 2560 * 2 + 4095 * 61_440 + 61_440
    lo = d.least_seconds(197e12, 819e9)
    assert lo == pytest.approx(d.bytes / 819e9)
