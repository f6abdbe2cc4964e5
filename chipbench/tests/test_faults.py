"""A whole run of the harness on the CPU at a tiny size, past the look
for a chip: a sound engine comes out ``correct``, and an engine broken
underneath the timed path does not — once for each fault a one-chip
serving cell can have: a decode step that returns its cache unchanged,
and a token altered where it is sampled."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import tiny

NAMES = ["ttft_p95_s", "itl_p95_ms", "output_tok_s", "setup_s"]
CELLS = {"paged-open": ("paged", "open", None, 4),
         "contig-closed": ("contig", "closed", 128, 2)}


def _run(cell, fault=None, seed=2**31 + 11):
    kind, loop, window, kv = CELLS[cell]
    c = tiny.config(kind=kind, window=window, kv_heads=kv)
    return run.run(c, tiny.mix(loop), seed=seed, seconds=1.5, trace=False,
                   metric_names=NAMES, base_arch=tiny.base_arch(c),
                   fault=fault, log=lambda *a, **k: None)


def state_unchanged(eng):
    """Every decode dispatch hands back the cache it was given (a copy:
    the dispatch donates the one it is given)."""
    dispatch = eng._dispatch

    def broken(kind, fn, params, *args):
        if kind != "decode":
            return dispatch(kind, fn, params, *args)
        kept = jax.tree.map(jnp.copy, args[1])
        return dispatch(kind, fn, params, *args)[0], kept
    eng._dispatch = broken


def token_altered(eng):
    """Every sampled token is moved to the next id."""
    sample = eng._sample
    vocab = eng.cfg.vocab_size
    eng._sample = lambda *a: (sample(*a) + 1) % vocab


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checked"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == set(NAMES)
    assert list(out)[-1] == "checked"


@pytest.mark.parametrize("fault", [state_unchanged, token_altered],
                         ids=["state-unchanged", "token-altered"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_broken_timed_path_is_not_correct(cell, fault):
    out = _run(cell, fault=fault)
    assert not out["correct"], out["checked"]
    assert out["checked"]["max_logit_gap"]["value"] > \
        out["checked"]["max_logit_gap"]["limit"]
