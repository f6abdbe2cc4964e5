"""Serve phi3-mini-3.8b at its published widths on a TPU and check what
comes out: the quickest proof that the serving path still starts on the
chip.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips (one host, 2x2)

One chip serves two phases through the normal entry points
(``ServingEngine(params, cfg, EngineConfig(...))``, ``submit``, ``run``),
each with the same 8 seeded requests (prompts of 128-1024 tokens, 32 new
tokens each, greedy) and a KV cache that fills what the weights leave:

  (a) the contiguous cache under the blocking scheduler;
  (b) the paged cache under the chunked scheduler.

``--four-chips`` runs only the paths that exist across chips: the model
on a ``mesh=(1, 4)`` engine against the same requests on device 0 alone,
then a 1-prefill/3-decode ``ClusterEngine`` with one chip per worker.

Checks (any failure exits 1): every request finishes with its full token
budget; exactly one decode dispatch per engine step; every logit the
engine samples from is finite; each request's first-token logits match a
batch-1 ``model.prefill`` of the same prompt within ``LOGIT_TOL``; on four
chips, the greedy tokens agree with device 0 alone. Weights are random,
drawn from ``--seed``. Without a TPU the script exits 2 and prints no
result; on success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "phi3-mini-3.8b"
N_REQUESTS = 8
PROMPT_LENS = (128, 1024)   # inclusive range of seeded prompt lengths
NEW_TOKENS = 32
SLOTS, CAPACITY = 8, 2048   # KV for 8 x 2048 positions: 6.0 GiB of bf16
                            # beside 7.1 GiB of weights on a 15.75 GiB chip
CHUNK_TOKENS = 256

# First-token logits vs a batch-1 prefill of the same prompt, as
# rms(engine - reference) / rms(reference). Both sides run bf16 weights
# and activations; they differ in shape (bucket padding, chunked
# prefill-over-cache, a sharded mesh), so XLA sums in another order and
# rounds the residual stream differently: about one bf16 rounding
# (2^-8, 0.4%) per op that feeds the residual, ~4 per layer. Over 32
# layers these add like a random walk, sqrt(128) * 0.4% ~ 4.4% of the
# logits' RMS; 0.1 leaves twice that. A wrong cache row, position or
# mask moves the logits by O(1) of their RMS (another prompt's logits
# sit at sqrt(2)), far outside it.
LOGIT_TOL = 0.1


def make_prompts(vocab: int, seed: int, n: int = N_REQUESTS,
                 lens: tuple = PROMPT_LENS):
    """``n`` seeded prompts with lengths drawn from ``lens`` (inclusive)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(k), dtype=np.int32)
            for k in rng.integers(lens[0], lens[1] + 1, size=n)]


def reference_first_logits(params, cfg, prompts):
    """Each prompt's next-token logits from a batch-1, unpadded
    ``model.prefill`` — independent of the engine's bucketing, caches and
    schedulers."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as MD

    fn = jax.jit(lambda p, toks: MD.prefill(
        p, cfg, {"tokens": toks}, toks.shape[1])[0][0])
    return [np.asarray(fn(params, jnp.asarray(p[None])), np.float32)
            for p in prompts]


def _engines(engine):
    """The ServingEngines behind ``engine`` (itself, or a cluster's
    workers)."""
    if hasattr(engine, "prefill_workers"):
        return [w.eng for w in engine.prefill_workers + engine.decode_workers]
    return [engine]


def serve_phase(engine, prompts, new_tokens: int) -> dict:
    """Serve ``prompts`` through ``engine`` (a ``ServingEngine`` or a
    ``ClusterEngine``) and record, beside the finished requests, the
    logits each request's first token was sampled from and whether every
    logits output of every dispatch was finite."""
    import jax.numpy as jnp
    import numpy as np

    first: dict = {}
    finite = {"ok": True}
    for eng in _engines(engine):
        dispatch, sample_first = eng._dispatch, eng._sample_first

        def checked(kind, fn, params, *args, _dispatch=dispatch):
            out = _dispatch(kind, fn, params, *args)
            finite["ok"] &= bool(jnp.isfinite(out[0]).all())
            return out

        def recorded(req, seed, logits, n_prompt, _first=sample_first):
            first[req.rid] = np.asarray(logits[0], np.float32)
            return _first(req, seed, logits, n_prompt)

        eng._dispatch, eng._sample_first = checked, recorded
    reqs = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
    t0 = time.perf_counter()
    engine.run()
    wall = time.perf_counter() - t0
    return {"requests": reqs, "first_logits": first, "finite": finite["ok"],
            "summary": engine.summary(), "wall_s": wall}


def logit_error(got, want) -> float:
    """rms(got - want) / rms(want)."""
    import numpy as np
    return float(np.sqrt(np.mean(np.square(got - want))
                         / np.mean(np.square(want))))


def check_phase(res: dict, ref_logits, new_tokens: int) -> list[str]:
    """Failed checks of one served phase (empty when all hold)."""
    fails = []
    reqs = res["requests"]
    short = [r.rid for r in reqs
             if len(r.output) != new_tokens or r.truncated_from is not None]
    if short:
        fails.append(f"requests without their full {new_tokens}-token "
                     f"budget: {short}")
    s = res["summary"]
    if s["dispatches_per_step"] != 1.0 or s["decode_steps"] < 1:
        fails.append(f"decode dispatches per step "
                     f"{s['dispatches_per_step']} over {s['decode_steps']} "
                     "steps (want exactly 1)")
    if not res["finite"]:
        fails.append("non-finite logits")
    errs = [logit_error(res["first_logits"][r.rid], ref)
            for r, ref in zip(reqs, ref_logits)]
    res["logit_err"] = max(errs)
    if res["logit_err"] > LOGIT_TOL:
        fails.append(f"first-token logits off the batch-1 prefill: "
                     f"{res['logit_err']:.4f} > {LOGIT_TOL}")
    return fails


class CompileClock:
    """Seconds JAX spent in backend compilation (set-up time)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def _report(name, res, clock, dev):
    s = res["summary"]
    toks = sum(len(r.output) for r in res["requests"])
    print(f"[{name}] served {s['requests']} requests, {toks} tokens in "
          f"{res['wall_s']:.2f} s wall ({clock.lap():.1f} s of it "
          f"compiling) | kv={s['kv_cache']} "
          f"scheduler={s.get('scheduler', 'blocking')} "
          f"resident KV {s['resident_kv_bytes'] / 2**30:.3f} GiB | "
          f"decode dispatches/step {s['dispatches_per_step']} | "
          f"first-token logit error {res['logit_err']:.5f} "
          f"(tol {LOGIT_TOL})", flush=True)
    stats = dev.memory_stats() or {}
    print(f"[{name}] device peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 0)}", flush=True)


def one_chip(params, cfg, prompts, ref, clock, dev, *, slots=SLOTS,
             capacity=CAPACITY, new_tokens=NEW_TOKENS,
             chunk_tokens=CHUNK_TOKENS) -> list[str]:
    """Both one-chip phases; returns their failed checks."""
    from repro.serving import EngineConfig, ServingEngine

    fails = []
    phases = {
        "contiguous+blocking": EngineConfig(
            max_batch=slots, max_seq_len=capacity,
            max_new_tokens=new_tokens),
        "paged+chunked": EngineConfig(
            max_batch=slots, max_seq_len=capacity,
            max_new_tokens=new_tokens, kv_cache="paged",
            scheduler="chunked", chunk_tokens=chunk_tokens),
    }
    outputs = {}
    for name, ecfg in phases.items():
        eng = ServingEngine(params, cfg, ecfg)
        print(f"[{name}] slots x capacity {slots} x {capacity}, KV "
              f"{eng.summary()['contiguous_kv_bytes']} bytes", flush=True)
        res = serve_phase(eng, prompts, new_tokens)
        fails += [f"{name}: {f}" for f in check_phase(res, ref, new_tokens)]
        _report(name, res, clock, dev)
        outputs[name] = [r.output for r in res["requests"]]
        del eng, res
        gc.collect()
    a, b = outputs.values()
    print(f"greedy streams identical across the two phases: "
          f"{sum(x == y for x, y in zip(a, b))}/{len(a)}", flush=True)
    return fails


def four_chips(params, cfg, prompts, ref, clock, dev, *, slots=SLOTS,
               capacity=CAPACITY, new_tokens=NEW_TOKENS) -> list[str]:
    """The mesh engine and the cluster, each against device 0 alone;
    returns their failed checks."""
    import dataclasses

    import jax
    from repro.serving import (ClusterConfig, ClusterEngine, EngineConfig,
                               ServingEngine)

    fails = []
    ecfg = EngineConfig(max_batch=slots, max_seq_len=capacity,
                        max_new_tokens=new_tokens)
    runs = {}
    for name, make in (
            ("device0", lambda: ServingEngine(params, cfg, ecfg)),
            ("mesh(1,4)", lambda: ServingEngine(
                params, cfg, dataclasses.replace(ecfg, mesh=(1, 4)))),
            ("cluster 1p+3d", lambda: ClusterEngine(
                params, cfg, ecfg, ClusterConfig(
                    n_prefill=1, n_decode=3,
                    devices=tuple(jax.devices()[:4]))))):
        eng = make()
        res = serve_phase(eng, prompts, new_tokens)
        fails += [f"{name}: {f}" for f in check_phase(res, ref, new_tokens)]
        if name == "cluster 1p+3d":
            s = res["summary"]
            print(f"[{name}] handoffs {s['handoffs']} KV moved "
                  f"{s['kv_transfer_bytes']} bytes", flush=True)
        _report(name, res, clock, dev)
        runs[name] = res
        del eng
        gc.collect()
    base = runs.pop("device0")
    for name, res in runs.items():
        errs = [logit_error(res["first_logits"][r.rid],
                            base["first_logits"][r.rid])
                for r in base["requests"]]
        same = [r.output == s.output
                for r, s in zip(base["requests"], res["requests"])]
        firsts = all(r.output[0] == s.output[0]
                     for r, s in zip(base["requests"], res["requests"]))
        print(f"[{name}] vs device0: first-token logit error "
              f"{max(errs):.5f}, identical greedy streams "
              f"{sum(same)}/{len(same)}", flush=True)
        if max(errs) > LOGIT_TOL:
            fails.append(f"{name}: first-token logits off device 0: "
                         f"{max(errs):.4f} > {LOGIT_TOL}")
        if not firsts:
            fails.append(f"{name}: first greedy tokens differ from "
                         "device 0")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh and cluster paths on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.configs import registry
    from repro.models import model as MD

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this check runs only on a TPU", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devs)}",
              file=sys.stderr)
        return 2
    print(f"device_kind {dev.device_kind} x {len(devs)}", flush=True)

    clock = CompileClock()
    cfg = registry.get_config(ARCH)
    params = MD.init_params(jax.random.PRNGKey(args.seed), cfg)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"{ARCH}: {n_params} parameters ({cfg.dtype}), init "
          f"{clock.lap():.1f} s compiling", flush=True)
    prompts = make_prompts(cfg.vocab_size, args.seed)
    print(f"prompt lengths {[len(p) for p in prompts]}, {NEW_TOKENS} new "
          "tokens each", flush=True)
    ref = reference_first_logits(params, cfg, prompts)
    print(f"reference prefill: {clock.lap():.1f} s compiling", flush=True)

    run = four_chips if args.four_chips else one_chip
    fails = run(params, cfg, prompts, ref, clock, dev)
    if fails:
        for f in fails:
            print(f"FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
