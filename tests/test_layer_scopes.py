"""Layer scopes in the served programs: the device programs name their
layers with ``jax.named_scope``, which the profiler keeps in each
operation's ``op_name``. Checked on the lowered programs the engine
dispatches, with the operand shapes it logged."""
from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as MD
from repro.serving import EngineConfig, ServingEngine
from repro.serving import kv_cache as KV


@pytest.fixture(scope="module")
def engines():
    cfg = registry.get_smoke_config("qwen1.5-0.5b").replace(dtype="float32")
    params = MD.init_params(jax.random.PRNGKey(3), cfg)
    out = {}
    for kv, sched in (("contiguous", "blocking"), ("paged", "chunked"),
                      ("contiguous", "chunked")):
        eng = ServingEngine(params, cfg, EngineConfig(
            max_batch=2, max_seq_len=64, max_new_tokens=3, kv_cache=kv,
            scheduler=sched, chunk_tokens=16))
        rng = np.random.default_rng(0)
        for n in (6, 20):
            eng.submit(rng.integers(0, cfg.vocab_size, size=n))
        eng.run()
        out[(kv, sched)] = eng
    return out


def _scopes(text: str) -> set:
    """Path components of every op name in a lowered module's text."""
    names = re.findall(r'loc\("([^"]*)"', text)
    return {part for n in names for part in n.split("/")}


def _lowered(eng, kind):
    entry = next(e for e in eng.dispatch_log if e["kind"] == kind)
    fn = jax.jit(eng._closures[kind])
    return fn.lower(eng.params, *entry["spec"]).as_text(debug_info=True)


@pytest.mark.parametrize("kv", ["contiguous", "paged"])
def test_decode_program_names_its_layers(engines, kv):
    eng = engines[(kv, "blocking" if kv == "contiguous" else "chunked")]
    assert {"attention", "kv_write", "mlp", "logits", "attn_proj"} <= \
        _scopes(_lowered(eng, "decode"))


@pytest.mark.parametrize("kv", ["contiguous", "paged"])
def test_chunk_program_and_its_splice_name_their_layers(engines, kv):
    """A chunk program attends the cached history (the paged gather
    included) and returns its KV rows; the splice program that lands
    them is scoped ``kv_write``."""
    eng = engines[(kv, "chunked")]
    assert {"attention", "mlp", "logits"} <= \
        _scopes(_lowered(eng, f"chunk_{kv}"))
    rows = jax.ShapeDtypeStruct((eng.cfg.n_layers, 1, 16,
                                 eng.cfg.n_kv_heads, eng.cfg.d_head),
                                np.float32)
    i32 = jax.ShapeDtypeStruct((), np.int32)
    view = eng.kv.chunk_view(0)
    k, v = view["k"], view["v"]
    if kv == "paged":
        lanes = jax.ShapeDtypeStruct(     # one lane group: NB < 128
            (1, eng.kv.num_blocks), np.int32)
        text = KV.splice_positions.lower(k, v, rows, rows, lanes, i32, i32)
    else:
        text = KV.splice_chunk.lower(k, v, rows, rows, i32, i32, i32)
    assert "kv_write" in _scopes(text.as_text(debug_info=True))


def test_prefill_program_names_its_layers(engines):
    eng = engines[("contiguous", "blocking")]
    assert {"attention", "kv_write", "mlp", "logits"} <= \
        _scopes(_lowered(eng, "prefill"))
