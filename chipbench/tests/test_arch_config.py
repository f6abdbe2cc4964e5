"""Each dense configuration file is taken by ``system.arch_config`` as it
stands, and refused where the program would run something else: a
changed width, a tied embedding, a head size, a bias, a window the
program does not run."""
from __future__ import annotations

import json

import pytest

from chipbench import run, system

CONFIGS = ["phi3-mini-3.8b", "h2o-danube-1.8b"]
WIDTH_KEYS = ["hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "intermediate_size", "vocab_size",
              "rope_theta"]


def _config(name: str) -> dict:
    return json.loads((run.HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_the_file_as_it_stands_is_taken(name):
    c = _config(name)
    arch = system.arch_config(c)
    assert (arch.d_model, arch.n_layers, arch.d_ff, arch.dtype) == (
        c["hidden_size"], c["num_hidden_layers"], c["intermediate_size"],
        "bfloat16")


@pytest.mark.parametrize("key", WIDTH_KEYS)
@pytest.mark.parametrize("name", CONFIGS)
def test_a_changed_width_is_refused(name, key):
    c = _config(name)
    c[key] = c[key] * 2
    with pytest.raises(ValueError, match=f"{key}={c[key]} but the program"):
        system.arch_config(c)


@pytest.mark.parametrize("change,match", [
    ({"tie_word_embeddings": True}, "tied embeddings"),
    ({"head_dim": 64}, "head size"),
    ({"attention_bias": True}, "attention bias"),
    ({"sliding_window": 1024}, "sliding_window=1024 but the program")],
    ids=["tied", "head-size", "bias", "window"])
@pytest.mark.parametrize("name", CONFIGS)
def test_what_the_program_does_not_run_is_refused(name, change, match):
    with pytest.raises(ValueError, match=match):
        system.arch_config(dict(_config(name), **change))


def test_a_window_is_refused_where_it_would_mask():
    """phi3's 2047 is taken only because no position of its 2048-slot
    engine reaches it; danube's 4096 is what the program runs, so no
    window at all is refused."""
    c = _config("phi3-mini-3.8b")
    c["engine"] = dict(c["engine"], max_seq_len=4096)
    with pytest.raises(ValueError, match="sliding_window=2047"):
        system.arch_config(c)
    with pytest.raises(ValueError, match="sliding_window=None"):
        system.arch_config(dict(_config("h2o-danube-1.8b"),
                                sliding_window=None))
