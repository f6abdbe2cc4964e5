"""A cell small enough for a CPU test: the smoke-sized phi3 or danube
shapes through the same harness, engine and reference."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench import run, system

# the cluster cell whose cell file and traffic mix are in the harness but
# which is not among the benchmark's workloads: it has not run sound on
# four chips (PERF.md, Open questions)
DISAGG = {"name": "phi3-disagg-4chip", "config": "phi3-mini-3.8b",
          "traffic": "disagg", "chips": 4}


def config(kind: str = "paged", dtype: str = "float32", window=None,
           kv_heads: int = 4) -> dict:
    if kind == "paged":
        engine = {"kv_cache": "paged", "kv_block_size": 16,
                  "scheduler": "chunked", "chunk_tokens": 32,
                  "max_batch": 4, "max_seq_len": 128, "sample": "greedy"}
    else:
        engine = {"kv_cache": "contiguous", "scheduler": "blocking",
                  "max_batch": 4, "max_seq_len": 128, "sample": "greedy"}
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": kv_heads, "head_dim": 16,
            "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
            "sliding_window": window, "tie_word_embeddings": False,
            "torch_dtype": dtype, "family": "dense",
            "registry_arch": "phi3-mini-3.8b", "engine": engine,
            "check": {"max_logit_gap": 0.05, "sample_requests": 3}}


def cluster_config(dtype: str = "float32") -> dict:
    """The paged tiny cell served as a cluster of one prefill and three
    decode workers, one device each (the cluster takes the blocking
    scheduler)."""
    c = config(kind="paged", dtype=dtype)
    c["engine"]["scheduler"] = "blocking"
    c["cluster"] = {"n_prefill": 1, "n_decode": 3}
    return c


def base_arch(c: dict):
    """The program's smoke-sized model with the tiny config's sizes."""
    system.import_program()
    from repro.configs import registry
    return registry.get_smoke_config(c["registry_arch"]).replace(
        n_kv_heads=c["num_key_value_heads"],
        sliding_window=c["sliding_window"], dtype=c["torch_dtype"])


def mix(loop: str = "open") -> dict:
    m = {"ramp_s": 0.3, "drain_cap_s": 10.0,
         "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                    "min": 8, "max": 64, "quantum": 8},
         "output": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                    "min": 4, "max": 32, "quantum": 1}}
    if loop == "open":
        m.update(loop="open", rate_rps=20.0)
    else:
        m.update(loop="closed", clients=4, residual_start=True)
    return m


def bench_with(directory: Path, cell: dict) -> Path:
    """A copy of ``BENCHMARK.json`` in ``directory`` with ``cell`` among
    its workloads."""
    bench = json.loads(run.BENCH.read_text())
    bench["workloads"].append(cell)
    path = directory / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path
