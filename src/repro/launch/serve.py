"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``.

Stands up the continuous-batching engine (serving/engine.py) on a model
from the registry — optionally from a training checkpoint — and drives a
synthetic request workload, reporting the paper's serving metrics (TTFT,
tokens/s, QPS).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import registry
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as MD
from repro.serving import EngineConfig, ServingEngine


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from the latest checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-cache", default="contiguous",
                    choices=("contiguous", "paged"),
                    help="KV-cache backend (paged = block tables)")
    ap.add_argument("--kv-block-size", type=int, default=16)
    ap.add_argument("--sample", default="greedy",
                    choices=("greedy", "temperature"))
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    params = MD.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt_dir:
        from repro.optim import AdamW, OptConfig
        like = {"params": params, "opt": AdamW(OptConfig()).init(params)}
        step, got = CheckpointManager(args.ckpt_dir).restore_latest(like)
        if got is not None:
            params = got["params"]
            print(f"serving weights from checkpoint step {step}")

    eng = ServingEngine(params, cfg, EngineConfig(
        max_batch=args.slots, max_seq_len=args.capacity,
        max_new_tokens=args.max_new, kv_cache=args.kv_cache,
        kv_block_size=args.kv_block_size, sample=args.sample,
        temperature=args.temperature, top_k=args.top_k, seed=args.seed))
    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        eng.submit(rng.integers(0, cfg.vocab_size, size=args.prompt_len))
    done = eng.run()
    s = eng.summary()
    print(f"served {s['requests']} requests / {s['tokens']} tokens | "
          f"{s['tokens_per_s']:.1f} tok/s | {s['qps']:.2f} QPS | "
          f"mean TTFT {s['mean_ttft_s']*1e3:.0f} ms | "
          f"mean latency {s['mean_latency_s']*1e3:.0f} ms | "
          f"kv={s['kv_cache']} resident "
          f"{s['resident_kv_bytes']/2**20:.1f} MiB "
          f"(dense {s['contiguous_kv_bytes']/2**20:.1f} MiB)")
    sample = done[0]
    print(f"sample output (rid 0): {sample.output}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
