"""A cluster cell through the whole harness on four CPU devices (a child
process: the device count is fixed when JAX starts): the cell's own file
turns its configuration into a ``ClusterEngine`` of one prefill and three
decode workers, one device each; a sound run comes out ``correct`` and
the fp8 control does not; a run broken underneath the timed path does
not, once for each fault a cluster cell can have (a decode step that
returns its cache unchanged, a token altered where it is sampled, the
KV handoff between chips left out); and with the program's telemetry on
the handoff spans reduce to ``kv_handoff_p95_ms`` and the watch prices
the dispatches of every worker."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json

import jax
import numpy as np

from chipbench import drive, families, run, system
from chipbench import spans as SP
from chipbench import trace as TR
from chipbench.tests import test_faults, tiny

NAMES = ["ttft_p95_s", "itl_p95_ms", "output_tok_s", "setup_s"]
devs = jax.devices()[:4]
out = {}


def kv_handoff_dropped(eng):
    # every packet lands in its decode worker's pool as zeros
    imp = eng.kv.import_slot

    def dropped(packet, *a, **k):
        packet = dict(packet, k=np.zeros_like(packet["k"]),
                      v=np.zeros_like(packet["v"]))
        return imp(packet, *a, **k)
    eng.kv.import_slot = dropped


def on_workers(fault):
    def apply(eng):
        for e in system.serving_engines(eng):
            fault(e)
    return apply


# prompts of every length in 8-90: more lengths than the warm grid holds,
# and KV handoffs of 1 to 6 blocks
MIX = tiny.mix("closed")
MIX["prompt"].update(min=8, max=90, quantum=1)


def one(c, **kw):
    r = run.run(c, MIX, seed=2**31 + 5, seconds=1.5,
                trace=False, metric_names=NAMES, devices=devs,
                base_arch=tiny.base_arch(c), log=lambda *a, **k: None, **kw)
    return {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"], "metrics": sorted(r["metrics"]),
            "count": r["device"]["count"],
            "compiles_in_window": r["compiles_in_window"],
            "gap": r["checked"]["max_logit_gap"],
            "control": r.get("control")}


c = tiny.cluster_config()
out["sound"] = one(c)
for name, fault in (("state-unchanged", test_faults.state_unchanged),
                    ("token-altered", test_faults.token_altered),
                    ("kv-handoff-dropped", kv_handoff_dropped)):
    out[name] = one(c, fault=on_workers(fault))
cb = tiny.cluster_config(dtype="bfloat16")
cb["check"].update(max_logit_gap=0.05, sample_requests=64)
out["control"] = one(cb, control=True)

# the program's telemetry on: its handoff spans, read from a trace
fam = families.load(c)
w = fam.make_weights(c, 3, dtype=c["torch_dtype"])
eng = system.make_engine(fam.program_params(w), c, tiny.base_arch(c),
                         telemetry=True, devices=devs)
out["devices"] = sorted({str(x.device) for x in eng.prefill_workers
                         + eng.decode_workers})
watch = system.Watch(eng, families.Needed(fam, c))
rng = np.random.default_rng(0)
jax.profiler.start_trace("trace")
with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
    for n in (20, 40, 60, 33, 47):
        eng.submit(rng.integers(0, 256, n, dtype=np.int32),
                   max_new_tokens=6)
    eng.run()
jax.profiler.stop_trace()
events = SP.extract(TR.find_xplane("trace"))
# the CPU keeps no device plane: one device operation stands in for it
events.append(["/device:TPU:0", "XLA Ops", "fusion", events[0][3], 1.0, {}])
red = SP.reduce(events, families.Needed(fam, c).scopes)
rec = drive.Record(sent=[], steps=[], w0=0.0, w1=1.0, spans=red)
out["handoffs"] = eng.handoffs
out["handoff_spans"] = red["host_s"].get("cluster.kv.handoff", [])
out["kv_handoff_p95_ms"] = run.metric_reader("kv_handoff_p95_ms")(rec)
out["prefill_dispatches"] = red["dispatch"]["prefill"]["n"]
out["watched"] = [k for _, k, _ in watch.records] if watch.enabled else None
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    r = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, timeout=900, env=env,
                       cwd=tmp_path_factory.mktemp("cluster"))
    assert r.returncode == 0, r.stderr[-4000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_cell_file_makes_a_cluster_of_its_configuration(tmp_path):
    bench = tiny.bench_with(tmp_path, tiny.DISAGG)
    cell, c, mix, e2e, layer = run.load_cell("phi3-disagg-4chip", bench)
    assert cell["chips"] == 4 and mix["clients"] == 24
    assert c["cluster"] == {"n_prefill": 1, "n_decode": 3}
    assert c["engine"]["scheduler"] == "blocking"
    assert c["engine"]["kv_cache"] == "paged"
    assert c["engine"]["max_batch"] == 8 and \
        c["engine"]["max_seq_len"] == 2048
    assert "itl_p95_ms" in e2e
    # a cell without a file of its own serves its configuration as it is
    _, c, _, _, _ = run.load_cell("phi3-chat-paged")
    assert "cluster" not in c and c["engine"]["scheduler"] == "chunked"


def test_sound_cluster_run_is_correct_on_four_devices(child):
    s = child["sound"]
    assert s["correct"], s["gap"]
    assert s["attempted"] > 0 and s["failed"] == 0 and s["count"] == 4
    # warm-up served every prompt shape on every decode worker
    assert s["compiles_in_window"] == 0
    assert s["metrics"] == sorted(["ttft_p95_s", "itl_p95_ms",
                                   "output_tok_s", "setup_s"])
    assert len(child["devices"]) == 4


@pytest.mark.parametrize("fault", ["state-unchanged", "token-altered",
                                   "kv-handoff-dropped"])
def test_broken_cluster_is_not_correct(child, fault):
    r = child[fault]
    assert not r["correct"], r["gap"]
    assert r["gap"]["value"] > r["gap"]["limit"]


def test_fp8_control_fails_where_the_cluster_passes(child):
    r = child["control"]
    assert r["correct"], r["gap"]
    assert not r["control"]["correct"], r["control"]


def test_watch_records_every_worker_dispatch(child):
    kinds = child["watched"]
    assert kinds is not None
    assert kinds.count("prefill") == 5 and "decode" in kinds


def test_handoff_spans_reduce_to_kv_handoff_p95(child):
    d = child["handoff_spans"]
    assert len(d) == child["handoffs"] == child["prefill_dispatches"] == 5
    want = 1e3 * sorted(d)[-1] - 0.2 * 1e3 * (sorted(d)[-1] - sorted(d)[-2])
    assert child["kv_handoff_p95_ms"] == pytest.approx(want)
