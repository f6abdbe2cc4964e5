"""Serve a small model with batched requests through the
continuous-batching engine — the paper's cloud serving pattern
(prefill/decode interleave, slot reuse) at laptop scale — on both
KV-cache backends: the dense contiguous layout and the paged
(block-table) layout, which holds only the blocks requests actually
touch and frees them at retirement.

Also cross-checks the engine against the PIM-AI simulator: the same
workload is fed to the analytical model on two Table-1 profiles so you
can see what the engine's measured batching behaviour corresponds to on
the paper's hardware — including the resident-KV footprint the paged
layout saves.

Scheduling: ``--scheduler {blocking,chunked}`` selects the prefill
policy. ``blocking`` (default) runs each admitted prompt's whole
prefill in one dispatch; ``chunked`` streams prompts in as fixed
token-budget chunks, packing every engine step with (decode tokens for
all live slots) + (at most one prefill chunk) — the paper's
prefill/decode time-multiplexing at the scheduler level. The demo's
final section submits one long prompt ahead of the shorts and prints
the TTFT comparison: chunked cuts the shorts' tail TTFT because they
no longer wait behind the long prompt's monolithic prefill, while
greedy outputs stay bitwise identical.

Speculative decoding (``--scheduler speculative --gamma N``): a small
self-draft proposes N tokens per slot and the target verifies every
slot's candidate window in one dispatch; the demo's speculative section
prints the acceptance rate and tokens-per-target-dispatch next to the
TTFT comparison — greedy outputs stay bitwise identical to blocking at
any acceptance.

Prefix caching: the demo's prefix section submits requests sharing one
48-token preamble (a system prompt at laptop scale) twice through the
paged engine — cold, then with ``prefix_cache=True``. Warm admissions
content-hash the preamble's full blocks, splice the already-resident
shared blocks copy-on-write into the new slot's table, and prefill
only the unique tail; the printout shows the token hit rate, the
shared KV held resident, and the prompt tokens spliced instead of
prefilled — greedy outputs stay bitwise identical to cold prefill.

Disaggregation (``--cluster N_prefill,M_decode``): the same workload
through a ``ClusterEngine`` — prompts prefill on dedicated workers,
their KV hands off to the least-loaded decode worker (each worker a
``ServingEngine`` pinned to its own ``jax.devices()`` entry; run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to see real
multi-device placement), and one decode worker is drained mid-stream so
you can watch live slots migrate. Prints TTFT and the KV bytes that
crossed worker boundaries; greedy outputs stay bitwise identical to the
single engine.

Telemetry (``--telemetry``): the backend-comparison engines share one
``Telemetry`` hub — every engine phase (admit, prefill, decode
dispatch, KV commit/splice, sampling, retire) records a nested span and
every jitted dispatch is wall-timed with ``block_until_ready``. The
demo prints the top-5 slowest spans and the per-kind achieved-vs-
predicted calibration table that joins those wall times against the
static cost model's traced FLOPs/bytes — outputs stay bitwise
identical with telemetry on.

Run:  PYTHONPATH=src python examples/serve_batched.py
      PYTHONPATH=src python examples/serve_batched.py --telemetry
      PYTHONPATH=src python examples/serve_batched.py --scheduler chunked
      PYTHONPATH=src python examples/serve_batched.py \
          --scheduler speculative --gamma 4
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          PYTHONPATH=src python examples/serve_batched.py --cluster 1,2
"""
import argparse

import numpy as np
import jax

from repro.configs import registry
from repro.core import profiles as HW
from repro.core.simulator import LLMSimulator, SimConfig
from repro.models import model as MD
from repro.serving import (ClusterConfig, ClusterEngine, EngineConfig,
                           ServingEngine, Telemetry, dispatch_calibration,
                           format_calibration)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scheduler", default="blocking",
                    choices=["blocking", "chunked", "speculative"],
                    help="scheduling policy for the backend-comparison "
                         "runs")
    ap.add_argument("--gamma", type=int, default=4,
                    help="speculative: draft tokens per verify step")
    ap.add_argument("--cluster", default=None, metavar="N,M",
                    help="also run the disaggregated cluster demo with "
                         "N prefill and M decode workers (e.g. 1,2)")
    ap.add_argument("--telemetry", action="store_true",
                    help="instrument the backend-comparison runs with a "
                         "shared Telemetry hub and print the top-5 "
                         "slowest spans plus the per-kind achieved-vs-"
                         "predicted dispatch calibration table")
    args = ap.parse_args()

    cfg = registry.get_smoke_config("phi3-mini-3.8b")
    params = MD.init_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    lens = [int(rng.integers(8, 24)) for _ in range(10)]
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in lens]
    print(f"submitting 10 requests (prompt lens 8-24) into 4 slots "
          f"({args.scheduler} scheduler)...")

    tel = Telemetry() if args.telemetry else None
    tel_engines = []
    outputs = {}
    for kv in ("contiguous", "paged"):
        eng = ServingEngine(params, cfg, EngineConfig(
            max_batch=4, max_seq_len=96, max_new_tokens=12, kv_cache=kv,
            scheduler=args.scheduler, chunk_tokens=16,
            spec_gamma=args.gamma), telemetry=tel, telemetry_label=kv)
        tel_engines.append(eng)
        for p in prompts:
            eng.submit(p)
        eng.run()
        s = eng.summary()
        outputs[kv] = {r.rid: r.output for r in eng.finished}
        print(f"\n[{kv}] {s['requests']} requests, {s['tokens']} tokens, "
              f"{s['tokens_per_s']:.1f} tok/s, mean TTFT "
              f"{s['mean_ttft_s']*1e3:.0f} ms")
        print(f"  single-dispatch decode: {s['decode_dispatches']} "
              f"dispatches over {s['decode_steps']} steps "
              f"({s['dispatches_per_step']:.2f}/step), "
              f"{s['prefill_chunks']} prefill chunks")
        print(f"  resident KV: {s['resident_kv_bytes']/1024:.0f} KiB peak "
              f"vs {s['contiguous_kv_bytes']/1024:.0f} KiB dense "
              f"(max_batch x max_seq_len)")
    print(f"\npaged outputs bitwise-match contiguous: "
          f"{outputs['paged'] == outputs['contiguous']}")

    # -- telemetry: slowest spans + the measured-vs-predicted loop ----------
    # every engine phase above was wrapped in a span and every jitted
    # dispatch was wall-timed; join those wall times against the static
    # cost model's traced FLOPs/bytes for the exact same dispatch-log
    # entries and the model-error column tells you how far the jaxpr
    # cost model is from this machine (CI only gates finiteness).
    if tel is not None:
        print(f"\ntelemetry: {len(tel.tracer.spans)} spans across "
              f"{len(tel_engines)} engines; top-5 slowest:")
        for s in tel.tracer.slowest(5):
            print(f"  {s.wall_dur_s*1e3:9.2f} ms  [{s.tid}] "
                  f"{'  ' * s.depth}{s.name} ({s.cat})")
        print("\ndispatch calibration (host reference roofline):")
        print(format_calibration(dispatch_calibration(tel_engines, tel)))

    # -- scheduling: head-of-line blocking demo -----------------------------
    # one 72-token prompt queued ahead of the shorts: under the blocking
    # policy every short waits for its monolithic prefill; the chunked
    # policy streams it in 16-token chunks and the shorts' first tokens
    # come out almost immediately — same tokens, different schedule.
    print("\nscheduling: 1 long (72) prompt ahead of 6 shorts, "
          "chunk_tokens=16")
    hol_lens = [72] + [int(rng.integers(6, 14)) for _ in range(6)]
    hol_prompts = [rng.integers(0, cfg.vocab_size, size=n)
                   for n in hol_lens]
    hol_out = {}
    for sched in ("blocking", "chunked"):
        eng = ServingEngine(params, cfg, EngineConfig(
            max_batch=4, max_seq_len=96, max_new_tokens=8,
            scheduler=sched, chunk_tokens=16))
        for p in hol_prompts:
            eng.submit(p)
        eng.run()
        s = eng.summary()
        hol_out[sched] = {r.rid: r.output for r in eng.finished}
        short_ttft = [r.ttft_s for r in eng.finished if len(r.prompt) < 72]
        print(f"  [{sched:8s}] short-request TTFT p50 "
              f"{np.percentile(short_ttft, 50)*1e3:7.1f} ms, p99 "
              f"{np.percentile(short_ttft, 99)*1e3:7.1f} ms "
              f"({s['prefill_chunks']} prefill chunks)")
    print(f"  chunked outputs bitwise-match blocking: "
          f"{hol_out['chunked'] == hol_out['blocking']}")

    # -- scheduling: speculative decoding demo ------------------------------
    # the draft proposes gamma tokens per slot, the target verifies every
    # slot's candidate window in ONE dispatch — more than one token per
    # target weight stream when the draft is good (here: half-depth and
    # full-depth self-drafts), bitwise-identical tokens regardless.
    # Run in float32: the verify path (one softmax over history+window)
    # and the decode path (two-partial online merge) agree on every
    # argmax there, while bf16 ulp noise between the two summation
    # orders can flip near-ties — the equivalence the engine guarantees
    # (and CI enforces) is the float32 one.
    print(f"\nspeculative decoding: gamma={args.gamma}, self-draft, "
          "same 10-request workload, float32")
    # fresh float32 init (not a bf16 cast: quantized weights put logits
    # on a tie-prone grid that deflates the measured acceptance rate)
    cfg32 = cfg.replace(dtype="float32")
    params32 = MD.init_params(jax.random.PRNGKey(0), cfg32)
    spec_out = {}
    for label, layers in (("blocking", None), ("half-depth", 0),
                          ("full-depth", 99)):
        if layers is None:
            eng = ServingEngine(params32, cfg32, EngineConfig(
                max_batch=4, max_seq_len=96, max_new_tokens=12))
        else:
            eng = ServingEngine(params32, cfg32, EngineConfig(
                max_batch=4, max_seq_len=96, max_new_tokens=12,
                scheduler="speculative", spec_gamma=args.gamma,
                spec_draft_layers=layers))
        for p in prompts:
            eng.submit(p)
        eng.run()
        s = eng.summary()
        spec_out[label] = {r.rid: r.output for r in eng.finished}
        if layers is None:
            print(f"  [{label:10s}] 1.00 tokens/dispatch by definition "
                  f"({s['decode_dispatches']} target dispatches)")
        else:
            print(f"  [{label:10s}] acceptance rate "
                  f"{s['acceptance_rate']:.2f}, "
                  f"{s['accepted_tokens_per_step']:.2f} tokens/dispatch "
                  f"({s['verify_dispatches']} verifies + "
                  f"{s['draft_dispatches']} draft dispatches)")
    print(f"  speculative outputs bitwise-match blocking: "
          f"{spec_out['half-depth'] == spec_out['blocking']} / "
          f"{spec_out['full-depth'] == spec_out['blocking']}")

    # -- prefix caching demo ------------------------------------------------
    # eight requests sharing one 48-token preamble: warm admissions
    # splice the three already-resident shared blocks copy-on-write and
    # prefill only the unique tail — same tokens, a fraction of the
    # prefill work, and the pool holds one copy of the preamble.
    print("\nprefix cache: 8 requests sharing a 48-token preamble, "
          "10-block paged pool")
    pre = rng.integers(0, cfg.vocab_size, size=48)
    px_prompts = [np.concatenate(
        [pre, rng.integers(0, cfg.vocab_size,
                           size=int(rng.integers(4, 12)))])
        for _ in range(8)]
    px_out = {}
    for label, on in (("cold", False), ("warm", True)):
        eng = ServingEngine(params, cfg, EngineConfig(
            max_batch=4, max_seq_len=96, max_new_tokens=8,
            kv_cache="paged", kv_block_size=16, kv_blocks=10,
            prefix_cache=on))
        for p in px_prompts:
            eng.submit(p)
        eng.run()
        s = eng.summary()
        px_out[label] = {r.rid: r.output for r in eng.finished}
        print(f"  [{label}] {s['prefix_hits']}/{s['prefix_lookups']} "
              f"admissions hit, token hit rate {s['prefix_hit_rate']:.2f}, "
              f"{s['prefix_hit_tokens']} prompt tokens spliced instead "
              f"of prefilled, shared KV resident "
              f"{s['resident_shared_kv_bytes']/1024:.0f} KiB")
    print(f"  warm outputs bitwise-match cold prefill: "
          f"{px_out['warm'] == px_out['cold']}")

    # -- disaggregated prefill/decode cluster demo --------------------------
    if args.cluster:
        n_p, n_d = (int(x) for x in args.cluster.split(","))
        print(f"\ndisaggregated cluster: {n_p} prefill + {n_d} decode "
              f"workers over {len(jax.devices())} device(s), "
              "drain worker 0 mid-stream")
        clu = ClusterEngine(params, cfg, EngineConfig(
            max_batch=4, max_seq_len=96, max_new_tokens=12),
            ClusterConfig(n_prefill=n_p, n_decode=n_d))
        for p in prompts:
            clu.submit(p)
        for _ in range(3):   # let decode slots go live...
            clu.step()
        clu.drain_worker(0)  # ...then migrate them off worker 0
        clu.run()
        s = clu.summary()
        print(f"  {s['requests']} requests, {s['tokens']} tokens; "
              f"TTFT p50 {s['ttft_p50_s']*1e3:.0f} ms, "
              f"p99 {s['ttft_p99_s']*1e3:.0f} ms")
        print(f"  {s['handoffs']} prefill→decode handoffs + "
              f"{s['migrations']} drain migrations moved "
              f"{s['kv_transfer_bytes']/1024:.0f} KiB of KV between "
              "workers")
        clu_out = {r.rid: r.output for r in clu.finished}
        print(f"  cluster outputs bitwise-match single engine: "
              f"{clu_out == outputs['contiguous']}")
        for w in s["per_worker"]:
            print(f"    [{w['role']}-{w['idx']}] {w['device']} "
                  f"steps={w['steps']} "
                  f"dispatches={w['decode_dispatches']} "
                  f"{'draining' if w['draining'] else 'routable'}")

    # the same ragged continuous-batching workload on the paper's hardware
    full = registry.get_config("phi3-mini-3.8b")
    print("\nanalytical ragged serve (4 slots, W4A16, 12 new tokens):")
    for kv in ("contiguous", "paged"):
        for hw in (HW.PIM_AI_MOBILE, HW.SNAPDRAGON_8_GEN3):
            sim = LLMSimulator(full, hw, SimConfig(weight_bits=4))
            r = sim.serve(lens[:4], 12, kv_cache=kv)
            print(f"  {kv:10s} {hw.name:20s}: "
                  f"{r['tokens_per_s']:8.1f} tok/s, "
                  f"{r['energy_per_token_j']*1e3:6.1f} mJ/token, "
                  f"resident KV {r['resident_kv_bytes']/2**20:.0f} MiB "
                  f"(dense {r['contiguous_kv_bytes']/2**20:.0f} MiB)")


if __name__ == "__main__":
    main()
