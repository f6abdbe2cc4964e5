"""chip_smoke.py off the chip: its phase functions serve the smoke config
of phi3-mini-3.8b on CPU with every check passing (so the script cannot
rot between chip runs), and without a TPU — or without the repo beside
it — the script exits nonzero and prints no result."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import registry
from repro.models import model as MD
from repro.serving import EngineConfig, ServingEngine

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke_setup():
    smoke = _load_smoke()
    cfg = registry.get_smoke_config("phi3-mini-3.8b")   # bf16, like the chip
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    prompts = smoke.make_prompts(cfg.vocab_size, 0, n=4, lens=(8, 40))
    ref = smoke.reference_first_logits(params, cfg, prompts)
    return smoke, cfg, params, prompts, ref


@pytest.mark.parametrize("kv_cache,scheduler", [("contiguous", "blocking"),
                                                ("paged", "chunked")])
def test_smoke_phase_passes_on_phi3_smoke_config(smoke_setup, kv_cache,
                                                 scheduler):
    smoke, cfg, params, prompts, ref = smoke_setup
    eng = ServingEngine(params, cfg, EngineConfig(
        max_batch=3, max_seq_len=64, max_new_tokens=6, kv_cache=kv_cache,
        scheduler=scheduler, chunk_tokens=16))
    res = smoke.serve_phase(eng, prompts, 6)
    assert smoke.check_phase(res, ref, 6) == []
    assert len(res["first_logits"]) == len(prompts)
    assert res["logit_err"] <= smoke.LOGIT_TOL


def test_smoke_check_catches_a_wrong_first_token_distribution(smoke_setup):
    """The logits check is not vacuous: another prompt's reference fails."""
    smoke, cfg, params, prompts, ref = smoke_setup
    eng = ServingEngine(params, cfg, EngineConfig(max_batch=3,
                                                  max_seq_len=64,
                                                  max_new_tokens=6))
    res = smoke.serve_phase(eng, prompts, 6)
    fails = smoke.check_phase(res, ref[1:] + ref[:1], 6)
    assert any("first-token logits" in f for f in fails)


def test_one_chip_phases_pass_on_cpu(smoke_setup, capsys):
    """The whole one-chip path (both phases, their reports) at a small
    size."""
    smoke, cfg, params, prompts, ref = smoke_setup
    fails = smoke.one_chip(params, cfg, prompts, ref, smoke.CompileClock(),
                           jax.devices()[0], slots=3, capacity=64,
                           new_tokens=6, chunk_tokens=16)
    assert fails == []
    out = capsys.readouterr().out
    assert "[paged+chunked] served 4 requests, 24 tokens" in out
    assert "greedy streams identical across the two phases: 4/4" in out


FOUR_CHIP_SCRIPT = r"""
import sys
sys.path[:0] = [%(root)r, %(root)r + "/src"]
import jax
import chip_smoke as S
from repro.configs import registry
from repro.models import model as MD
cfg = registry.get_smoke_config("phi3-mini-3.8b")
params = MD.init_params(jax.random.PRNGKey(0), cfg)
prompts = S.make_prompts(cfg.vocab_size, 0, n=4, lens=(8, 40))
ref = S.reference_first_logits(params, cfg, prompts)
fails = S.four_chips(params, cfg, prompts, ref, S.CompileClock(),
                     jax.devices()[0], slots=3, capacity=64, new_tokens=6)
print("FAILS", fails)
"""


def test_four_chip_phases_pass_on_four_cpu_devices():
    """``--four-chips``'s mesh and cluster comparisons on four virtual
    CPU devices (a child process: the device count is fixed at JAX
    start-up)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c",
         FOUR_CHIP_SCRIPT % {"root": str(SCRIPT.parent)}],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FAILS []" in r.stdout
    assert "[mesh(1,4)] vs device0" in r.stdout
    assert "[cluster 1p+3d] vs device0" in r.stdout


@pytest.mark.parametrize("alone", [False, True])
def test_smoke_script_fails_without_tpu(tmp_path, alone):
    """No TPU (or no repo beside the script): nonzero exit, no JSON."""
    script = SCRIPT
    if alone:
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
