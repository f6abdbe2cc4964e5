"""Mesh-sharded serving engine: bitwise greedy parity with the
single-device engine on a real 8-device world (subprocess, the only
place tests override the device count), clean dispatch audit, and
per-device KV accounting."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

# the children compile on CPU placeholder devices; pinned here rather
# than inherited, so a parent on a chip machine cannot hand them the TPU
CPU_CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")

SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json

import numpy as np
import jax

from repro.configs import registry
from repro.core.costmodel import assert_no_drift, audit_engine
from repro.models import model as MD
from repro.serving.engine import EngineConfig, ServingEngine

LENS = [17, 33, 5, 64]


def drive(params, cfg, mesh, kv):
    eng = ServingEngine(params, cfg, EngineConfig(
        max_batch=4, max_seq_len=96, max_new_tokens=8, kv_cache=kv,
        mesh=mesh))
    rng = np.random.default_rng(0)
    for n in LENS:
        eng.submit(rng.integers(0, cfg.vocab_size, size=int(n)))
    done = eng.run()
    return eng, {r.rid: r.output for r in done}


out = {}
for arch, mesh in %(cases)s:
    cfg = registry.get_smoke_config(arch).replace(dtype="float32")
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    for kv in ("contiguous", "paged"):
        _, base = drive(params, cfg, None, kv)
        eng, got = drive(params, cfg, tuple(mesh), kv)
        assert_no_drift(audit_engine(eng))  # CI drift gate, mesh run
        s = eng.summary()
        out[f"{arch}/{kv}/{mesh[0]}x{mesh[1]}"] = {
            "bitwise": got == base,
            "dispatches_per_step": s["dispatches_per_step"],
            "mesh_devices": s["mesh_devices"],
            "kv_partitions": s["kv_partitions"],
            "resident_kv_bytes": s["resident_kv_bytes"],
            "resident_kv_bytes_per_device":
                s["resident_kv_bytes_per_device"],
        }
print("RESULT " + json.dumps(out))
"""


def run_sub(cases):
    script = SUBPROCESS_SCRIPT % {"cases": repr(cases)}
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=1800,
                       env=CPU_CHILD_ENV)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_mesh_engine_bitwise_and_audited_dense_and_moe():
    """(data=2, model=4) engine streams must be bitwise-identical to the
    single-device engine for dense and MoE smoke models on both KV
    backends, keep the one-jitted-dispatch-per-step invariant, and
    report per-device resident KV that tiles the total."""
    out = run_sub([("qwen1.5-0.5b", (2, 4)),
                   ("deepseek-moe-16b", (2, 2))])
    assert len(out) == 4
    for key, s in out.items():
        assert s["bitwise"], f"{key}: mesh stream diverged from 1-device"
        assert s["dispatches_per_step"] == pytest.approx(1.0), key
        assert s["mesh_devices"] in (4, 8)
        parts = s["kv_partitions"]
        assert parts > 1, f"{key}: KV not actually partitioned"
        per = s["resident_kv_bytes_per_device"]
        assert per * parts >= s["resident_kv_bytes"]
        assert per < s["resident_kv_bytes"]


@pytest.mark.slow
def test_mesh_engine_sequence_fallback_when_heads_do_not_divide():
    """model=8 over 4 KV heads forces the sequence-sharded online-softmax
    fallback; the stream must still match single-device greedy."""
    out = run_sub([("qwen1.5-0.5b", (1, 8))])
    for key, s in out.items():
        assert s["bitwise"], f"{key}: fallback stream diverged"
        assert s["dispatches_per_step"] == pytest.approx(1.0), key
