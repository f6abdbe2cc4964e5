"""Serving-engine batching benchmark: aligned vs. fully-ragged
workloads, contiguous vs. paged KV-cache backends, blocking vs.
chunked-prefill schedulers.

Invariants under test:

- ``ServingEngine.step`` issues exactly **one** jitted decode dispatch
  per step regardless of how many distinct slot positions are live (a
  position-grouped engine degrades to ``max_batch`` launches the moment
  prompt lengths diverge), and neither the cache backend nor the
  scheduler may change that (chunked adds at most one prefill-chunk
  dispatch per step).
- The paged (block-table) backend produces the same tokens as the
  contiguous backend while holding strictly fewer resident KV bytes on
  ragged workloads — the vLLM-style capacity win the paper's
  keep-KV-resident cloud argument (§1.2, §3.4) depends on.
- ``--scheduler chunked``: greedy outputs are bitwise identical to the
  blocking scheduler (hard-fail otherwise), while p99 TTFT of *short*
  requests on a mixed short/long workload drops strictly below
  blocking — the head-of-line-blocking win the paper's
  prefill/decode time-multiplexing argument (§4) predicts.
- ``--scheduler speculative``: greedy outputs are bitwise identical to
  blocking (hard-fail otherwise) on both cache backends, and on the
  high-acceptance workload (full-depth self-draft — the draft *is* the
  target) accepted-tokens/step must exceed 1.0 (hard-fail otherwise):
  each target weight stream commits more than one token, the LP-Spec
  energy/token win decode's memory-boundedness makes possible.
- ``--cluster``: the disaggregated ``ClusterEngine`` (1 prefill + 2
  decode workers over ``jax.devices()``; CI forces an 8-device CPU
  world via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)
  emits bitwise-identical greedy streams to the single blocking engine
  on both cache backends (hard-fail otherwise) — including a
  fault-injection run that kills a decode worker mid-stream, which
  must record at least one slot migration (hard-fail otherwise).
  TTFT/ITL/throughput and KV-handoff bytes are reported next to the
  single-engine baseline, and the analytical mirror
  (``LLMSimulator.serve(cluster=...)`` + the heterogeneous
  ``run_cloud_disaggregated`` TCO-per-QPS scenario) lands in the JSON.
- ``--prefix``: the ``'sharedprefix'`` trace replayed cold (prefix
  cache off) and warm (on) through the paged engine on a constrained
  block pool. Hard-fails unless greedy outputs are bitwise identical,
  warm p99 TTFT lands strictly below cold (suffix-only prefill and
  suffix-only reservations admit earlier), the warm engine's dispatch
  audit is clean with ≥ 1 paged-chunk dispatch, the analytical mirror
  reproduces the hit/miss/eviction ledger exactly, and the
  disaggregated cluster routes ≥ 1 admission by prefix affinity while
  staying bitwise. The hit-rate → TTFT → TCO-per-QPS sweep
  (``run_cloud_trace(prefix_sweep=...)``) lands in the JSON.

- ``--telemetry``: every scheduler on both KV backends under one shared
  ``Telemetry`` hub. Hard-fails unless instrumented outputs are bitwise
  identical to the uninstrumented engine, every dispatch audit is
  clean, every ``dispatch_log`` entry has its ``engine.dispatch.<kind>``
  span with its work arguments, the required prefill/decode/verify/
  draft/chunk kinds all ran, the metrics registry validates clean,
  and the Perfetto export passes schema validation. Writes ``<json>-trace.json`` (load in
  ui.perfetto.dev) and ``<json>-metrics.prom`` next to the JSON.

Also cross-checks against the analytical simulator's continuous-batching
path (``LLMSimulator.serve``) on Table-1 cloud profiles, which charges
the same single-dispatch ragged decode graph — and, under
``scheduler="chunked"``, the same chunk-interleaved schedule shape — as
the engine it models.

Run:  PYTHONPATH=src python -m benchmarks.run serving
      PYTHONPATH=src python -m benchmarks.bench_serving --json out.json
      PYTHONPATH=src python -m benchmarks.bench_serving \
          --scheduler chunked --json out-chunked.json
"""
from __future__ import annotations

import json
import time

import jax
import numpy as np

from benchmarks.common import print_table, r3
from repro.configs import registry
from repro.core import profiles as HW
from repro.core.simulator import LLMSimulator, SimConfig
from repro.models import model as MD
from repro.serving import (ClusterConfig, ClusterEngine, EngineConfig,
                           ServingEngine)

MODEL = "qwen1.5-0.5b"
MAX_BATCH = 4
MAX_SEQ = 96
N_NEW = 8
CHUNK = 16          # chunked-prefill token budget per step
# head-of-line workload: one batch-filling wave (no slot queueing, so
# TTFT isolates the prefill schedule) with a long prompt whose O(n^2)
# monolithic prefill genuinely dominates a decode step — the regime the
# chunked policy exists for (at 96-token capacity the effect hides
# behind per-dispatch overhead)
MIXED_SEQ = 1024
MIXED_LONG = 900
MIXED_CHUNK = 64
MIXED_SHORT_MAX = 14
GAMMA = 4           # speculative: draft tokens per verify step
N_PREFILL, N_DECODE = 1, 2   # --cluster topology
KILL_STEP = 3       # fault injection: kill a decode worker here
TRACE_SEED = 0      # --trace: seeded workload generator
TRACE_QUANTUM = 0.01         # virtual seconds per engine step
TRACE_NEW = 16               # engine cap; per-request budgets come
                             # from the trace itself
TRACE_TPUT_FLOOR = 0.95      # SLO policy may cost <= 5% vs FIFO
PREFIX_BLOCKS = 10           # --prefix: constrained pool, so admission
                             # waits on KV capacity and cached prefixes
                             # translate into earlier admission
PREFIX_SWEEP = (0, 16, 32, 48)   # shared-preamble lengths for the
                                 # hit-rate -> TTFT -> TCO sweep


def _workload(kind: str, rng):
    """Prompt lengths for one batch-filling wave of requests."""
    if kind == "aligned":
        return [12] * (2 * MAX_BATCH)
    if kind == "mixed":
        # one long prompt submitted *first*, shorts queued behind it in
        # the same slot wave — the head-of-line-blocking scenario
        # chunked prefill exists for
        return [MIXED_LONG] + list(
            rng.integers(6, MIXED_SHORT_MAX, size=MAX_BATCH - 1))
    return list(rng.integers(6, 32, size=2 * MAX_BATCH))  # fully ragged


def _drive(params, cfg, lens, rng, kv_cache, scheduler="blocking",
           max_seq=MAX_SEQ, chunk=CHUNK, gamma=GAMMA, draft_layers=0,
           mesh=None, out_engines=None, telemetry=None, label=None):
    eng = ServingEngine(params, cfg, EngineConfig(
        max_batch=MAX_BATCH, max_seq_len=max_seq, max_new_tokens=N_NEW,
        kv_cache=kv_cache, scheduler=scheduler, chunk_tokens=chunk,
        spec_gamma=gamma, spec_draft_layers=draft_layers, mesh=mesh),
        telemetry=telemetry, telemetry_label=label)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)) for n in lens]
    # warm every prefill bucket/chunk shape + the decode dispatch out of
    # the timing
    for p in prompts:
        eng.submit(p, max_new_tokens=2)
    eng.run()
    eng.finished.clear()
    eng.decode_dispatches = eng.decode_steps = eng.prefills = 0
    eng.prefill_chunk_dispatches = 0
    eng.draft_dispatches = eng.verify_dispatches = 0
    eng.spec_row_steps = eng.spec_committed = 0
    eng.spec_drafted = eng.spec_draft_accepted = 0

    t0 = time.time()
    for p in prompts:
        eng.submit(p)
    done = eng.run()
    if out_engines is not None:  # dispatch-audit hook for gate sections
        out_engines[kv_cache] = eng
    outputs = {r.rid: r.output for r in done}
    wall = time.time() - t0
    s = eng.summary()
    toks = s["tokens"]
    short = [r for r in done if len(r.prompt) < MIXED_LONG]
    return {
        "kv_cache": kv_cache,
        "scheduler": s["scheduler"],
        "requests": s["requests"],
        "tokens": toks,
        "tok_s": toks / wall if wall > 0 else float("inf"),
        "dispatches": s["decode_dispatches"],
        "steps": s["decode_steps"],
        "disp_per_step": s["dispatches_per_step"],
        "prefill_chunks": s["prefill_chunks"],
        "distinct_pos": len(set(int(n) for n in lens)),
        "ttft_p50_s": s["ttft_p50_s"],
        "ttft_p99_s": s["ttft_p99_s"],
        "mean_itl_s": s["mean_itl_s"],
        "short_ttft_p50_s": float(np.percentile(
            [r.ttft_s for r in short], 50)) if short else 0.0,
        "short_ttft_p99_s": float(np.percentile(
            [r.ttft_s for r in short], 99)) if short else 0.0,
        "resident_kv_bytes": s["resident_kv_bytes"],
        "contiguous_kv_bytes": s["contiguous_kv_bytes"],
        "mesh": s["mesh"],
        "mesh_devices": s["mesh_devices"],
        "kv_partitions": s["kv_partitions"],
        "resident_kv_bytes_per_device": s["resident_kv_bytes_per_device"],
        "draft_dispatches": s["draft_dispatches"],
        "verify_dispatches": s["verify_dispatches"],
        "accepted_tokens_per_step": s["accepted_tokens_per_step"],
        "acceptance_rate": s["acceptance_rate"],
        "outputs": outputs,
    }


def _drive_cluster(params, cfg, lens, rng, kv_cache, kill_step=None):
    """Drive the disaggregated cluster over one workload; optionally
    kill a decode worker mid-stream (fault injection). Returns the same
    metric dict shape as :func:`_drive` plus cluster accounting."""
    clu = ClusterEngine(
        params, cfg,
        EngineConfig(max_batch=MAX_BATCH, max_seq_len=MAX_SEQ,
                     max_new_tokens=N_NEW, kv_cache=kv_cache),
        ClusterConfig(n_prefill=N_PREFILL, n_decode=N_DECODE))
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)) for n in lens]
    # warm every worker's prefill bucket + decode dispatch compiles
    for p in prompts:
        clu.submit(p, max_new_tokens=2)
    clu.run()
    clu.finished.clear()
    clu.handoffs = clu.migrations = 0
    clu.kv_transfer_bytes = clu.migration_bytes = 0
    for w in clu.prefill_workers + clu.decode_workers:
        w.eng.decode_dispatches = w.eng.decode_steps = w.eng.prefills = 0

    t0 = time.time()
    for p in prompts:
        clu.submit(p)
    if kill_step is not None:
        steps = 0
        while clu.waiting or clu.pending or clu._any_live():
            clu.step()
            steps += 1
            if steps == kill_step:
                clu.kill_worker(0)  # preempt mid-stream: live slots
                # migrate to the surviving worker
    done = clu.run()
    wall = time.time() - t0
    s = clu.summary()
    return {
        "kv_cache": kv_cache,
        "scheduler": "cluster",
        "requests": s["requests"],
        "tokens": s["tokens"],
        "tok_s": s["tokens"] / wall if wall > 0 else float("inf"),
        "dispatches": s["decode_dispatches"],
        "steps": s["decode_steps"],
        "disp_per_step": s["dispatches_per_step"],
        "ttft_p50_s": s["ttft_p50_s"],
        "ttft_p99_s": s["ttft_p99_s"],
        "mean_itl_s": s["mean_itl_s"],
        "handoffs": s["handoffs"],
        "migrations": s["migrations"],
        "kv_transfer_bytes": s["kv_transfer_bytes"],
        "workers_alive": s["workers_alive"],
        "resident_kv_bytes": s["resident_kv_bytes"],
        "outputs": {r.rid: r.output for r in done},
    }


def _run_cluster_section(params, cfg, results, mismatched):
    """The --cluster benchmark: engine-level bitwise + fault-injection
    gates, then the analytical disaggregated mirror."""
    import jax as _jax

    from repro.core.scenarios import run_cloud_disaggregated

    results["cluster"] = {"devices": [str(d) for d in _jax.devices()],
                          "n_prefill": N_PREFILL, "n_decode": N_DECODE,
                          "engine": [], "analytical": []}
    rows = []
    lens = _workload("ragged", np.random.default_rng(6))
    for kv in ("contiguous", "paged"):
        base = _drive(params, cfg, lens, np.random.default_rng(7), kv,
                      "blocking")
        rows.append([kv, "single", base["requests"],
                     r3(base["ttft_p50_s"] * 1e3),
                     r3(base["mean_itl_s"] * 1e3), r3(base["tok_s"]),
                     0, 0, "0K"])
        runs = {
            "cluster": _drive_cluster(params, cfg, lens,
                                      np.random.default_rng(7), kv),
            "cluster+kill": _drive_cluster(params, cfg, lens,
                                           np.random.default_rng(7), kv,
                                           kill_step=KILL_STEP),
        }
        for label, m in runs.items():
            rows.append([kv, label, m["requests"],
                         r3(m["ttft_p50_s"] * 1e3),
                         r3(m["mean_itl_s"] * 1e3), r3(m["tok_s"]),
                         m["handoffs"], m["migrations"],
                         f"{m['kv_transfer_bytes'] / 1024:.0f}K"])
            same = m["outputs"] == base["outputs"]
            results["cluster"]["engine"].append(
                {"run": label, "kv_cache": kv,
                 "matches_single_engine": same,
                 **{k: v for k, v in m.items() if k != "outputs"}})
            if not same:
                mismatched.append(
                    f"cluster/{kv}/{label}: greedy outputs diverged "
                    "from the single blocking engine")
            if label == "cluster+kill" and m["migrations"] < 1:
                mismatched.append(
                    f"cluster/{kv}/fault-injection: no slot migration "
                    "recorded — the kill must preempt live slots")
    print_table(
        f"disaggregated cluster ({N_PREFILL} prefill + {N_DECODE} decode "
        f"workers over {len(_jax.devices())} devices; kill at step "
        f"{KILL_STEP})",
        ["kv_cache", "run", "reqs", "ttft p50 ms", "itl ms", "tok/s",
         "handoffs", "migrations", "KV moved"],
        rows)

    # analytical mirror on the paper's hardware + the heterogeneous
    # xPU-prefill/PIM-decode TCO scenario
    full = registry.get_config(MODEL)
    sim_rows = []
    lens4 = _workload("ragged", np.random.default_rng(6))[:MAX_BATCH]
    for kv in ("contiguous", "paged"):
        for hw in (HW.PIM_AI_CHIP, HW.DGX_H100):
            sim = LLMSimulator(full, hw, SimConfig())
            r = sim.serve(lens4, N_NEW, kv_cache=kv, max_seq_len=MAX_SEQ,
                          cluster=(N_PREFILL, N_DECODE))
            sim_rows.append(
                [kv, hw.name, r3(r["tokens_per_s"]),
                 r3(r["energy_per_token_j"] * 1e3),
                 f"{r['kv_transfer_bytes'] / 1024:.0f}K",
                 r3(r["kv_transfer_s"] * 1e3)])
            results["cluster"]["analytical"].append(
                {"kv_cache": kv, "profile": hw.name,
                 "tokens_per_s": r["tokens_per_s"],
                 "energy_per_token_j": r["energy_per_token_j"],
                 "kv_transfer_bytes": r["kv_transfer_bytes"],
                 "kv_transfer_s": r["kv_transfer_s"],
                 "ttft_s": r["ttft_s"]})
    print_table(
        f"analytical cluster serve (Table-1 profiles, "
        f"{N_PREFILL}p+{N_DECODE}d)",
        ["kv_cache", "profile", "tok/s", "mJ/token", "KV moved",
         "xfer ms"], sim_rows)

    het = run_cloud_disaggregated("llama2-70b", "gqa")
    results["cluster"]["disaggregated_tco"] = {
        "model": het["model"], "attn": het["attn"],
        "engines_per_xpu": het["engines_per_xpu"],
        "kv_transfer": het["kv_transfer"],
        "tco_per_qps": {k: v["tco_per_qps"]
                        for k, v in het["tco"].items()},
        "ratios": het["ratios"],
    }
    print_table(
        "heterogeneous xPU-prefill + PIM-decode (llama2-70b/gqa, "
        "1000 in / 100 out)",
        ["system", "tco $/qps"],
        [[k, r3(v["tco_per_qps"])] for k, v in het["tco"].items()]
        + [["engines/xpu", r3(het["engines_per_xpu"])],
           ["KV moved/batch", f"{het['kv_transfer']['bytes']/2**30:.1f}G"]])


def _run_mesh_section(params, cfg, results, mismatched, mesh):
    """The --mesh benchmark: one ServingEngine on a (data, model) device
    mesh, hard-gating

    - bitwise-identical greedy outputs vs. the single-device engine on
      both KV backends (tensor/sequence parallelism must not change a
      single token of the greedy stream),
    - the one-jitted-dispatch-per-step invariant (sharding happens
      *inside* the dispatch, never as extra launches),
    - a clean dispatch audit (the traced closures stay meshless, so the
      static pricer sees the exact same jaxprs),
    - actual KV partitioning: resident KV bytes per device strictly
      below the total,

    then mirrors the same shape analytically (``LLMSimulator.serve``
    with ``mesh=``) and lands the ``run_cloud_mesh`` scaling sweep in
    the JSON artifact."""
    import jax as _jax

    from repro.core import costmodel as CM
    from repro.core.scenarios import run_cloud_mesh

    d, m = mesh
    results["mesh"] = {"mesh": [d, m],
                       "devices": [str(x) for x in _jax.devices()],
                       "engine": [], "analytical": []}
    rows = []
    lens = _workload("ragged", np.random.default_rng(8))
    for kv in ("contiguous", "paged"):
        base = _drive(params, cfg, lens, np.random.default_rng(9), kv)
        engines = {}
        mm = _drive(params, cfg, lens, np.random.default_rng(9), kv,
                    mesh=mesh, out_engines=engines)
        for label, r in (("single", base), (f"{d}x{m}", mm)):
            rows.append([kv, label, r["requests"], r3(r["tok_s"]),
                         r3(r["disp_per_step"]), r["kv_partitions"],
                         f"{r['resident_kv_bytes'] / 1024:.0f}K",
                         f"{r['resident_kv_bytes_per_device'] / 1024:.0f}K"])
        same = mm["outputs"] == base["outputs"]
        results["mesh"]["engine"].append(
            {"kv_cache": kv, "matches_single_device": same,
             **{k: v for k, v in mm.items() if k != "outputs"}})
        if not same:
            mismatched.append(
                f"mesh/{kv}: greedy outputs diverged from the "
                "single-device engine")
        if mm["disp_per_step"] != 1.0:
            mismatched.append(
                f"mesh/{kv}: {mm['disp_per_step']:.2f} dispatches/step "
                "— sharding must stay inside the single dispatch")
        if mm["resident_kv_bytes_per_device"] >= mm["resident_kv_bytes"]:
            mismatched.append(
                f"mesh/{kv}: per-device resident KV not below total — "
                "the cache is not actually partitioned")
        try:
            audit = CM.audit_engine(engines[kv])
            CM.assert_no_drift(audit)
        except Exception as e:  # noqa: BLE001 — audit drift is the gate
            mismatched.append(f"mesh/{kv}: dispatch audit failed: {e}")
    print_table(
        f"mesh-sharded engine (data={d} x model={m} over "
        f"{len(_jax.devices())} devices)",
        ["kv_cache", "run", "reqs", "tok/s", "disp/step", "kv parts",
         "resident KV", "KV/device"],
        rows)

    # analytical mirror on the paper's hardware: same (d, m) split
    full = registry.get_config(MODEL)
    sim_rows = []
    lens4 = _workload("ragged", np.random.default_rng(8))[:MAX_BATCH]
    for kv in ("contiguous", "paged"):
        for hw in (HW.PIM_AI_CHIP, HW.DGX_H100):
            sim = LLMSimulator(full, hw, SimConfig())
            r = sim.serve(lens4, N_NEW, kv_cache=kv, max_seq_len=MAX_SEQ,
                          mesh=mesh)
            sim_rows.append(
                [kv, hw.name, r3(r["tokens_per_s"]),
                 r3(r["energy_per_token_j"] * 1e3), r["kv_partitions"],
                 f"{r['resident_kv_bytes_per_device'] / 2**20:.0f}M"])
            results["mesh"]["analytical"].append(
                {"kv_cache": kv, "profile": hw.name,
                 "tokens_per_s": r["tokens_per_s"],
                 "energy_per_token_j": r["energy_per_token_j"],
                 "ttft_s": r["ttft_s"],
                 "kv_partitions": r["kv_partitions"],
                 "resident_kv_bytes_per_device":
                     r["resident_kv_bytes_per_device"]})
    print_table(
        f"analytical mesh serve (Table-1 profiles, data={d} x model={m})",
        ["kv_cache", "profile", "tok/s", "mJ/token", "kv parts",
         "KV/device"],
        sim_rows)

    # mesh-shape scaling sweep: the quantitative few-engines-many-DIMMs
    # argument (model axis ~linear per device, data axis pays weight
    # replication)
    sweep = run_cloud_mesh("llama2-70b", "gqa", n_out=16, batch=4)
    results["mesh"]["scaling"] = sweep
    print_table(
        "mesh scaling sweep (llama2-70b/gqa, PIM-AI chip)",
        ["mesh", "tok/s", "tok/s/device", "J/token", "KV/device"],
        [[k, r3(v["tokens_per_s"]),
          r3(v["tokens_per_s"] / v["devices"]),
          r3(v["energy_per_token_j"]),
          f"{v['resident_kv_bytes_per_device'] / 2**30:.1f}G"]
         for k, v in sweep["meshes"].items()])


def _run_trace_section(params, cfg, results, mismatched, trace_name):
    """The --trace benchmark: replay one seeded multi-tenant trace under
    FIFO (blocking) and the SLO-aware scheduler, hard-gating

    - bitwise-identical greedy outputs (preemption is migration through
      the packet path, never token loss),
    - the high-priority tenant's p99 TTFT within its SLO under the SLO
      policy (with at least one preemption actually exercised),
    - aggregate token throughput within ``TRACE_TPUT_FLOOR`` of FIFO,
    - the analytical mirror (``LLMSimulator.serve(trace=...)``)
      reproducing the SLO run's admission order and preemption log
      exactly,

    and lands the trace schema + both runs + the priced
    ``run_cloud_trace`` scenario in the JSON artifact."""
    from repro.core.scenarios import run_cloud_trace
    from repro.serving.workload import make_named_trace, replay

    tr = make_named_trace(trace_name, vocab_size=cfg.vocab_size,
                          seed=TRACE_SEED)
    results["trace"] = {"schema": tr.schema(),
                        "step_quantum_s": TRACE_QUANTUM, "runs": {}}
    # tenant -> (priority, ttft SLO) from the trace itself; the gated
    # tenant is the highest-priority one with a finite TTFT SLO
    tenant_slo: dict[str, tuple[int, float]] = {}
    for r in tr.schema()["requests"]:
        tenant_slo[r["tenant"]] = (r["priority"], r["slo_ttft_s"])
    gated = max((t for t, (_, s) in tenant_slo.items()
                 if s != float("inf")),
                key=lambda t: tenant_slo[t][0], default=None)

    runs = {}
    rows = []
    for label, sched in (("fifo", "blocking"), ("slo", "slo")):
        eng = ServingEngine(params, cfg, EngineConfig(
            max_batch=MAX_BATCH, max_seq_len=MAX_SEQ,
            max_new_tokens=TRACE_NEW, scheduler=sched, eos_token=-1))
        rep = replay(eng, tr, step_quantum_s=TRACE_QUANTUM)
        runs[label] = rep
        s = rep["summary"]
        for tenant, b in s["by_tenant"].items():
            _, slo_s = tenant_slo.get(tenant, (0, float("inf")))
            rows.append(
                [label, tenant, b["requests"],
                 r3(b["ttft_p50_s"] * 1e3), r3(b["ttft_p99_s"] * 1e3),
                 "-" if slo_s == float("inf") else r3(slo_s * 1e3),
                 r3(b["slo_attainment"]), b["preemptions"]])
        results["trace"]["runs"][label] = {
            "scheduler": sched, "steps": rep["steps"],
            "tokens": rep["tokens"], "decode_steps": rep["decode_steps"],
            "preemptions": s["preemptions"],
            "admission_order": rep["admission_order"],
            "preemption_log": rep["preemption_log"],
            "by_tenant": s["by_tenant"],
            "by_priority": s["by_priority"],
        }
    print_table(
        f"trace replay ({trace_name!r}, seed {TRACE_SEED}, "
        f"{len(tr.requests)} requests over {tr.horizon_s}s, "
        f"quantum {TRACE_QUANTUM}s)",
        ["run", "tenant", "reqs", "ttft p50 ms", "ttft p99 ms",
         "slo ms", "attain", "preempt"],
        rows)

    fifo, slo = runs["fifo"], runs["slo"]
    if slo["outputs"] != fifo["outputs"]:
        mismatched.append(
            f"trace/{trace_name}: SLO outputs diverged from FIFO — "
            "preemption must be lossless migration")
    if slo["summary"]["preemptions"] < 1:
        mismatched.append(
            f"trace/{trace_name}: SLO policy made no preemptions — "
            "the overload never exercised the packet path")
    if gated is not None:
        slo_s = tenant_slo[gated][1]
        p99 = slo["summary"]["by_tenant"][gated]["ttft_p99_s"]
        if p99 > slo_s:
            mismatched.append(
                f"trace/{trace_name}: {gated} p99 TTFT {p99:.4f}s "
                f"misses its {slo_s:.3f}s SLO under the SLO scheduler")
        p99_fifo = fifo["summary"]["by_tenant"][gated]["ttft_p99_s"]
        results["trace"]["gate"] = {
            "tenant": gated, "slo_ttft_s": slo_s,
            "slo_p99_ttft_s": p99, "fifo_p99_ttft_s": p99_fifo,
            "fifo_violates": p99_fifo > slo_s,
        }
    tput_ratio = ((slo["tokens"] / slo["steps"])
                  / (fifo["tokens"] / fifo["steps"]))
    results["trace"]["throughput_ratio_slo_vs_fifo"] = tput_ratio
    if tput_ratio < TRACE_TPUT_FLOOR:
        mismatched.append(
            f"trace/{trace_name}: SLO throughput ratio {tput_ratio:.3f} "
            f"below the {TRACE_TPUT_FLOOR} floor vs FIFO")

    # analytical mirror: same trace, same (real) scheduler policy over
    # the simulator's slot mechanism — the schedule must be identical
    sim = LLMSimulator(registry.get_config(MODEL), HW.PIM_AI_SERVER,
                       SimConfig())
    r_sim = sim.serve(trace=tr, scheduler="slo", max_batch=MAX_BATCH,
                      max_seq_len=MAX_SEQ, step_quantum_s=TRACE_QUANTUM)
    mirror_ok = (r_sim["admission_order"] == slo["admission_order"]
                 and r_sim["preemption_log"] == slo["preemption_log"]
                 and r_sim["steps"] == slo["steps"])
    if not mirror_ok:
        mismatched.append(
            f"trace/{trace_name}: analytical mirror schedule diverged "
            "from the engine replay (admissions/preemptions/steps)")
    results["trace"]["mirror"] = {
        "profile": HW.PIM_AI_SERVER.name, "matches_engine": mirror_ok,
        "steps": r_sim["steps"], "preemptions": r_sim["preemptions"],
        "energy_per_token_j": r_sim["energy_per_token_j"],
        "energy_j": r_sim["energy_j"],
    }
    print_table(
        "analytical mirror (SLO schedule priced on "
        f"{HW.PIM_AI_SERVER.name})",
        ["matches engine", "steps", "preempt", "J/token"],
        [[str(mirror_ok), r_sim["steps"], r_sim["preemptions"],
          r3(r_sim["energy_per_token_j"])]])

    # price the same trace shape at cloud scale (xPU vs PIM vs the
    # autoscaled disaggregated split)
    priced = run_cloud_trace(trace=trace_name, seed=TRACE_SEED)
    results["trace"]["pricing"] = {
        k: {kk: vv for kk, vv in priced[k].items() if kk != "tco"}
        for k in ("dgx-h100", "pim-ai-engine", "disaggregated")}
    results["trace"]["pricing"]["ratios"] = priced["ratios"]
    print_table(
        f"cloud pricing over the {trace_name!r} trace (llama2-70b/gqa)",
        ["system", "J/token", "tco $/qps", "slo attain"],
        [[k, r3(priced[k]["energy_per_token_j"]),
          r3(priced[k]["tco_per_qps"]),
          r3(priced[k]["slo_attainment"])]
         for k in ("dgx-h100", "pim-ai-engine", "disaggregated")])


def _run_prefix_section(params, cfg, results, mismatched):
    """The --prefix benchmark: replay the shared-preamble trace cold
    (prefix cache off) and warm (on) through the paged engine on the
    virtual clock, hard-gating

    - bitwise-identical greedy outputs (copy-on-write splicing never
      changes tokens),
    - warm p99 TTFT strictly below cold — suffix-only prefill plus
      suffix-only reservations admit earlier under a constrained pool,
    - a clean dispatch audit on the warm engine with at least one
      paged-chunk dispatch (suffix prefill prices through the same
      traced chunk closure as everything else),
    - the analytical mirror reproducing the warm engine's admission
      order and full hit/eviction ledger exactly,
    - the disaggregated cluster routing at least one admission by
      prefix affinity while staying bitwise with the cold run,

    and lands the hit-rate -> TTFT -> TCO-per-QPS sweep in the JSON."""
    from repro.core import costmodel as CM
    from repro.core.scenarios import run_cloud_trace
    from repro.serving.workload import make_named_trace, replay

    tr = make_named_trace("sharedprefix", vocab_size=cfg.vocab_size,
                          seed=TRACE_SEED)
    results["prefix"] = {"trace": "sharedprefix", "seed": TRACE_SEED,
                         "kv_blocks": PREFIX_BLOCKS, "runs": {}}
    runs = {}
    rows = []
    engines = {}
    for label, on in (("cold", False), ("warm", True)):
        eng = ServingEngine(params, cfg, EngineConfig(
            scheduler="blocking", kv_cache="paged", kv_block_size=16,
            kv_blocks=PREFIX_BLOCKS, prefix_cache=on, eos_token=-1,
            max_batch=MAX_BATCH, max_seq_len=MAX_SEQ,
            max_new_tokens=TRACE_NEW))
        rep = replay(eng, tr, step_quantum_s=TRACE_QUANTUM)
        engines[label] = eng
        runs[label] = rep
        s = rep["summary"]
        rows.append([label, s["requests"], r3(s["ttft_p50_s"] * 1e3),
                     r3(s["ttft_p99_s"] * 1e3), s["prefix_hits"],
                     r3(s["prefix_hit_rate"]), s["prefix_evictions"],
                     f"{s['resident_shared_kv_bytes'] / 1024:.0f}K"])
        results["prefix"]["runs"][label] = {
            "prefix_cache": on, "steps": rep["steps"],
            "ttft_p50_s": s["ttft_p50_s"], "ttft_p99_s": s["ttft_p99_s"],
            "prefix_hits": s["prefix_hits"],
            "prefix_lookups": s["prefix_lookups"],
            "prefix_hit_rate": s["prefix_hit_rate"],
            "prefix_evictions": s["prefix_evictions"],
            "resident_shared_kv_bytes": s["resident_shared_kv_bytes"],
            "prefill_chunks": s["prefill_chunks"],
        }
    print_table(
        f"prefix cache ('sharedprefix' trace, seed {TRACE_SEED}, "
        f"{len(tr.requests)} requests, {PREFIX_BLOCKS}-block pool)",
        ["run", "reqs", "ttft p50 ms", "ttft p99 ms", "hits", "hit rate",
         "evictions", "shared KV"],
        rows)

    cold, warm = runs["cold"], runs["warm"]
    if warm["outputs"] != cold["outputs"]:
        mismatched.append(
            "prefix: warm greedy outputs diverged from cold prefill — "
            "COW splicing must never change tokens")
    ws, cs = warm["summary"], cold["summary"]
    if ws["prefix_hits"] < 1:
        mismatched.append("prefix: warm run recorded no prefix hits")
    if not ws["ttft_p99_s"] < cs["ttft_p99_s"]:
        mismatched.append(
            f"prefix: warm p99 TTFT {ws['ttft_p99_s']:.4f}s not below "
            f"cold {cs['ttft_p99_s']:.4f}s")
    results["prefix"]["gate"] = {
        "warm_matches_cold": warm["outputs"] == cold["outputs"],
        "warm_ttft_p99_s": ws["ttft_p99_s"],
        "cold_ttft_p99_s": cs["ttft_p99_s"],
    }

    # dispatch audit: suffix-only prefill must price through the traced
    # paged-chunk closure with zero drift
    try:
        audit = CM.audit_engine(engines["warm"])
        CM.assert_no_drift(audit)
        if audit["kinds"].get("chunk_paged", 0) < 1:
            mismatched.append(
                "prefix: warm engine dispatched no paged prefill "
                "chunks — suffix prefill must ride the chunk closure")
        results["prefix"]["audit_kinds"] = audit["kinds"]
    except Exception as e:  # noqa: BLE001 — audit drift is the gate
        mismatched.append(f"prefix: dispatch audit failed: {e}")

    # analytical mirror: same PrefixIndex over virtual block ids — the
    # hit/miss/eviction schedule must replay exactly, not approximately
    sim = LLMSimulator(registry.get_config(MODEL), HW.PIM_AI_SERVER,
                       SimConfig())
    r_sim = sim.serve(trace=tr, scheduler="blocking", kv_cache="paged",
                      kv_block_size=16, kv_blocks=PREFIX_BLOCKS,
                      prefix_cache=True, max_batch=MAX_BATCH,
                      max_seq_len=MAX_SEQ, step_quantum_s=TRACE_QUANTUM)
    mirror_ok = (r_sim["admission_order"] == warm["admission_order"]
                 and r_sim["steps"] == warm["steps"]
                 and r_sim["prefix_hits"] == ws["prefix_hits"]
                 and r_sim["prefix_hit_tokens"] == ws["prefix_hit_tokens"]
                 and r_sim["prefix_evictions"] == ws["prefix_evictions"])
    if not mirror_ok:
        mismatched.append(
            "prefix: analytical mirror diverged from the warm engine "
            f"(sim hits={r_sim['prefix_hits']} evictions="
            f"{r_sim['prefix_evictions']} vs engine "
            f"{ws['prefix_hits']}/{ws['prefix_evictions']})")
    results["prefix"]["mirror"] = {
        "matches_engine": mirror_ok, "steps": r_sim["steps"],
        "prefix_hits": r_sim["prefix_hits"],
        "prefix_hit_rate": r_sim["prefix_hit_rate"],
        "prefix_evictions": r_sim["prefix_evictions"],
        "energy_per_token_j": r_sim["energy_per_token_j"],
    }
    print_table(
        f"analytical mirror (warm schedule priced on "
        f"{HW.PIM_AI_SERVER.name})",
        ["matches engine", "steps", "hits", "hit rate", "evictions"],
        [[str(mirror_ok), r_sim["steps"], r_sim["prefix_hits"],
          r3(r_sim["prefix_hit_rate"]), r_sim["prefix_evictions"]]])

    # disaggregated path: the router must send shared-prefix admissions
    # to the prefill worker already holding the blocks, bitwise intact
    clu = ClusterEngine(
        params, cfg,
        EngineConfig(scheduler="blocking", kv_cache="paged",
                     kv_block_size=16, kv_blocks=PREFIX_BLOCKS + 2,
                     prefix_cache=True, eos_token=-1, max_batch=MAX_BATCH,
                     max_seq_len=MAX_SEQ, max_new_tokens=TRACE_NEW),
        ClusterConfig(n_prefill=2, n_decode=2))
    rep_c = replay(clu, tr, step_quantum_s=TRACE_QUANTUM)
    sc = rep_c["summary"]
    if rep_c["outputs"] != cold["outputs"]:
        mismatched.append(
            "prefix: cluster warm outputs diverged from cold prefill")
    if sc["prefix_routed"] < 1:
        mismatched.append(
            "prefix: cluster router never routed by prefix affinity")
    results["prefix"]["cluster"] = {
        "n_prefill": 2, "n_decode": 2,
        "matches_cold": rep_c["outputs"] == cold["outputs"],
        "prefix_routed": sc["prefix_routed"],
        "prefix_hits": sc["prefix_hits"],
        "prefix_hit_rate": sc["prefix_hit_rate"],
        "handoffs": sc["handoffs"],
    }
    print_table(
        "cluster prefix affinity (2 prefill + 2 decode)",
        ["matches cold", "routed", "hits", "hit rate", "handoffs"],
        [[str(rep_c["outputs"] == cold["outputs"]), sc["prefix_routed"],
          sc["prefix_hits"], r3(sc["prefix_hit_rate"]), sc["handoffs"]]])

    # cloud pricing: hit rate -> TTFT -> TCO-per-QPS, constant prompt
    # length with a growing shared share (llama2-70b/gqa analytical)
    priced = run_cloud_trace(seed=TRACE_SEED, prefix_sweep=PREFIX_SWEEP)
    results["prefix"]["sweep"] = priced["prefix_sweep"]
    print_table(
        "hit-rate TCO sweep (llama2-70b/gqa, constant prompt length, "
        "growing shared preamble)",
        ["prefix len", "hit rate", "ttft p99 ms", "qps", "J/token",
         "tco $/qps"],
        [[p["prefix_len"], r3(p["prefix_hit_rate"]),
          r3(p["ttft_p99_s"] * 1e3), r3(p["qps_sustained"]),
          r3(p["energy_per_token_j"]), r3(p["tco_per_qps"])]
         for p in priced["prefix_sweep"]])


def _run_telemetry_section(params, cfg, results, mismatched, json_path):
    """The --telemetry benchmark: drive blocking + chunked + speculative
    on both KV backends with one shared :class:`Telemetry` hub,
    hard-gating

    - bitwise-identical greedy outputs vs. the uninstrumented engine on
      every (backend, scheduler) pair — observation must never perturb
      the stream,
    - a clean dispatch audit on every instrumented engine (the spans
      wrap the *same* logged dispatches the static pricer traces),
    - every ``dispatch_log`` entry has its ``engine.dispatch.<kind>``
      span carrying the dispatch's work (``rows``, ``keys``,
      ``new_tokens``), and across the three schedulers every kind of
      the required set {prefill, decode, verify, draft_prefill,
      draft_decode, chunk_<backend>} appears per backend,
    - a healthy metrics registry (no NaN/negative histogram state) and
      a Perfetto export that passes schema validation,

    and writes the trace-event JSON + Prometheus text dump next to the
    main JSON artifact."""
    from repro.core import costmodel as CM
    from repro.serving import Telemetry, validate_trace_events

    tel = Telemetry()
    results["telemetry"] = {"backends": {}, "artifacts": {},
                            "spans": 0, "metric_series": 0}
    lens = _workload("ragged", np.random.default_rng(10))
    rows = []
    for kv in ("contiguous", "paged"):
        observed = set()
        for sched in ("blocking", "chunked", "speculative"):
            base = _drive(params, cfg, lens, np.random.default_rng(11),
                          kv, sched)
            out = {}
            m = _drive(params, cfg, lens, np.random.default_rng(11),
                       kv, sched, telemetry=tel, label=f"{kv}-{sched}",
                       out_engines=out)
            eng = out[kv]
            observed |= {e["kind"] for e in eng.dispatch_log}
            same = m["outputs"] == base["outputs"]
            if not same:
                mismatched.append(
                    f"telemetry/{kv}/{sched}: instrumented outputs "
                    "diverged from the uninstrumented engine")
            audit_ok = True
            try:
                CM.assert_no_drift(CM.audit_engine(eng))
            except Exception as e:  # noqa: BLE001 — drift is the gate
                audit_ok = False
                mismatched.append(
                    f"telemetry/{kv}/{sched}: dispatch audit failed: {e}")
            spans = [s for s in tel.tracer.spans
                     if s.tid == eng.tel_label and s.cat == "dispatch"]
            spanned = sum(
                s.name == f"engine.dispatch.{e['kind']}"
                and {"rows", "keys", "new_tokens"} <= set(s.labels)
                for s, e in zip(spans, eng.dispatch_log))
            total = len(eng.dispatch_log)
            if spanned != total or len(spans) != total or total == 0:
                mismatched.append(
                    f"telemetry/{kv}/{sched}: {spanned}/{total} "
                    "dispatch-log entries have their dispatch span")
            agg = tel.engine_aggregates(eng.tel_label)
            rows.append([kv, sched, m["requests"], str(same),
                         str(audit_ok), f"{spanned}/{total}",
                         agg["spans"], r3(agg["span_wall_s"] * 1e3)])

        required = {"prefill", "decode", "verify", "draft_prefill",
                    "draft_decode", f"chunk_{kv}"}
        for kind in sorted(required - observed):
            mismatched.append(
                f"telemetry/{kv}: dispatch kind {kind!r} never ran, so "
                "its span was never checked")
        results["telemetry"]["backends"][kv] = {
            "kinds_observed": sorted(observed),
            "kinds_required": sorted(required),
        }
    print_table(
        "telemetry coverage (shared hub, ragged workload)",
        ["kv_cache", "scheduler", "reqs", "bitwise", "audit", "spanned",
         "spans", "span ms"],
        rows)

    problems = tel.metrics.validate()
    if problems:
        mismatched.append(f"telemetry: unhealthy metrics registry: "
                          f"{problems}")
    trace = tel.tracer.trace_events()
    trace_problems = validate_trace_events(trace)
    if trace_problems:
        mismatched.append(f"telemetry: Perfetto export failed schema "
                          f"validation: {trace_problems}")
    results["telemetry"]["spans"] = len(tel.tracer.spans)
    results["telemetry"]["metric_series"] = len(tel.metrics.snapshot())
    results["telemetry"]["metrics_problems"] = problems
    results["telemetry"]["trace_problems"] = trace_problems

    if json_path:
        stem = json_path[:-5] if json_path.endswith(".json") else json_path
        trace_path = f"{stem}-trace.json"
        prom_path = f"{stem}-metrics.prom"
        with open(trace_path, "w") as f:
            json.dump(trace, f)
        with open(prom_path, "w") as f:
            f.write(tel.metrics.to_prometheus() + "\n")
        results["telemetry"]["artifacts"] = {"trace": trace_path,
                                             "metrics": prom_path}
        print(f"\n[wrote {trace_path}]\n[wrote {prom_path}]")


def run(json_path: str | None = None, scheduler: str = "blocking",
        cluster: bool = False, trace: str | None = None,
        prefix: bool = False, mesh: tuple | None = None,
        telemetry: bool = False):
    cfg = registry.get_smoke_config(MODEL).replace(dtype="float32")
    params = MD.init_params(jax.random.PRNGKey(0), cfg)

    results = {"model": MODEL, "max_batch": MAX_BATCH, "max_seq": MAX_SEQ,
               "n_new": N_NEW, "scheduler": scheduler, "chunk_tokens": CHUNK,
               "spec_gamma": GAMMA,
               "engine": [], "analytical": [], "head_of_line": [],
               "speculative": []}
    rows = []
    mismatched = []
    if telemetry:
        # the --telemetry flavor is its own CI step: every scheduler on
        # both KV backends under one shared Telemetry hub, with
        # bitwise/audit/dispatch-span/schema gates, writing
        # the Perfetto trace + Prometheus dump next to the JSON
        _run_telemetry_section(params, cfg, results, mismatched,
                               json_path)
        if json_path:
            with open(json_path, "w") as f:
                json.dump(results, f, indent=2, default=float)
            print(f"\n[wrote {json_path}]")
        if mismatched:
            raise SystemExit(f"serving invariants violated: {mismatched}")
        return results
    if mesh is not None:
        # the --mesh flavor is its own CI step: one engine on a
        # (data, model) device mesh with bitwise/dispatch/audit/
        # partition gates plus the analytical mirror and scaling sweep
        _run_mesh_section(params, cfg, results, mismatched, mesh)
        if json_path:
            with open(json_path, "w") as f:
                json.dump(results, f, indent=2, default=float)
            print(f"\n[wrote {json_path}]")
        if mismatched:
            raise SystemExit(f"serving invariants violated: {mismatched}")
        return results
    if prefix:
        # the --prefix flavor is its own CI step: warm-vs-cold replay of
        # the shared-preamble trace with bitwise/TTFT/audit/mirror/
        # affinity gates plus the hit-rate TCO sweep
        _run_prefix_section(params, cfg, results, mismatched)
        if json_path:
            with open(json_path, "w") as f:
                json.dump(results, f, indent=2, default=float)
            print(f"\n[wrote {json_path}]")
        if mismatched:
            raise SystemExit(f"serving invariants violated: {mismatched}")
        return results
    if trace is not None:
        # the --trace flavor is its own CI step: one seeded multi-tenant
        # trace, FIFO vs SLO, with the analytical mirror + pricing
        _run_trace_section(params, cfg, results, mismatched, trace)
        if json_path:
            with open(json_path, "w") as f:
                json.dump(results, f, indent=2, default=float)
            print(f"\n[wrote {json_path}]")
        if mismatched:
            raise SystemExit(f"serving invariants violated: {mismatched}")
        return results
    if cluster:
        # the --cluster flavor is its own CI step: run only the
        # disaggregated section (the single-engine baselines it needs
        # are driven inside it)
        _run_cluster_section(params, cfg, results, mismatched)
        if json_path:
            with open(json_path, "w") as f:
                json.dump(results, f, indent=2, default=float)
            print(f"\n[wrote {json_path}]")
        if mismatched:
            raise SystemExit(f"serving invariants violated: {mismatched}")
        return results
    for kind in ("aligned", "ragged"):
        lens = _workload(kind, np.random.default_rng(0))
        per_backend = {}
        for kv in ("contiguous", "paged"):
            m = _drive(params, cfg, lens, np.random.default_rng(1), kv,
                       scheduler)
            per_backend[kv] = m
            rows.append([kind, kv, m["requests"], m["distinct_pos"],
                         m["tokens"], r3(m["tok_s"]), m["dispatches"],
                         r3(m["disp_per_step"]),
                         f"{m['resident_kv_bytes'] / 1024:.0f}K",
                         f"{m['contiguous_kv_bytes'] / 1024:.0f}K"])
            results["engine"].append(
                {"workload": kind,
                 **{k: v for k, v in m.items() if k != "outputs"}})
        same = (per_backend["paged"]["outputs"]
                == per_backend["contiguous"]["outputs"])
        results["engine"].append({"workload": kind,
                                  "paged_matches_contiguous": same})
        if not same:
            mismatched.append(kind)
    print_table(
        f"engine batching ({MODEL} smoke, {MAX_BATCH} slots, "
        f"{scheduler} scheduler, CPU numbers)",
        ["workload", "kv_cache", "reqs", "distinct lens", "tokens", "tok/s",
         "dispatches", "disp/step", "resident KV", "dense KV"],
        rows)

    if scheduler == "chunked":
        # head-of-line-blocking demonstration: one long prompt queued
        # ahead of shorts; chunked must (a) emit bitwise-identical
        # tokens and (b) cut the shorts' tail TTFT strictly below
        # blocking, on both cache backends.
        hol_rows = []
        lens = _workload("mixed", np.random.default_rng(2))
        for kv in ("contiguous", "paged"):
            per_sched = {}
            for sched in ("blocking", "chunked"):
                m = _drive(params, cfg, lens, np.random.default_rng(3), kv,
                           sched, max_seq=MIXED_SEQ, chunk=MIXED_CHUNK)
                per_sched[sched] = m
                hol_rows.append(
                    [kv, sched, m["prefill_chunks"],
                     r3(m["ttft_p50_s"] * 1e3),
                     r3(m["short_ttft_p50_s"] * 1e3),
                     r3(m["short_ttft_p99_s"] * 1e3),
                     r3(m["mean_itl_s"] * 1e3)])
                results["head_of_line"].append(
                    {"kv_cache": kv, "scheduler": sched,
                     **{k: v for k, v in m.items() if k != "outputs"}})
            same = (per_sched["chunked"]["outputs"]
                    == per_sched["blocking"]["outputs"])
            win = (per_sched["chunked"]["short_ttft_p99_s"]
                   < per_sched["blocking"]["short_ttft_p99_s"])
            results["head_of_line"].append(
                {"kv_cache": kv, "chunked_matches_blocking": same,
                 "chunked_short_p99_ttft_below_blocking": win})
            if not same:
                mismatched.append(f"mixed/{kv} (chunked vs blocking)")
            if not win:
                mismatched.append(
                    f"mixed/{kv}: chunked short-request p99 TTFT "
                    f"{per_sched['chunked']['short_ttft_p99_s']:.4f}s not "
                    f"below blocking "
                    f"{per_sched['blocking']['short_ttft_p99_s']:.4f}s")
        print_table(
            f"head-of-line blocking (mixed workload: 1x{MIXED_LONG}-token "
            f"prompt ahead of {MAX_BATCH - 1} shorts, "
            f"cap={MIXED_SEQ}, chunk={MIXED_CHUNK})",
            ["kv_cache", "scheduler", "chunks", "ttft p50 ms",
             "short p50 ms", "short p99 ms", "itl ms"],
            hol_rows)

    if scheduler == "speculative":
        # speculative decoding demonstration: (a) outputs must be
        # bitwise identical to blocking on both backends at any
        # acceptance; (b) on the high-acceptance workload (full-depth
        # self-draft — the draft IS the target) each target weight
        # stream must commit strictly more than one token.
        spec_rows = []
        lens = _workload("ragged", np.random.default_rng(4))
        for kv in ("contiguous", "paged"):
            base = _drive(params, cfg, lens, np.random.default_rng(5), kv,
                          "blocking")
            for label, draft_layers in (("half-depth", 0),
                                        ("full-depth", 99)):
                m = _drive(params, cfg, lens, np.random.default_rng(5),
                           kv, "speculative", gamma=GAMMA,
                           draft_layers=draft_layers)
                spec_rows.append(
                    [kv, label, m["verify_dispatches"],
                     m["draft_dispatches"],
                     r3(m["accepted_tokens_per_step"]),
                     r3(m["acceptance_rate"]), r3(m["tok_s"])])
                same = m["outputs"] == base["outputs"]
                results["speculative"].append(
                    {"kv_cache": kv, "draft": label,
                     "spec_matches_blocking": same,
                     **{k: v for k, v in m.items() if k != "outputs"}})
                if not same:
                    mismatched.append(
                        f"speculative/{kv}/{label}: greedy outputs "
                        "diverged from blocking")
                if (label == "full-depth"
                        and m["accepted_tokens_per_step"] <= 1.0):
                    mismatched.append(
                        f"speculative/{kv}/high-acceptance: "
                        f"{m['accepted_tokens_per_step']:.2f} accepted "
                        "tokens/step <= 1.0 — each weight stream must "
                        "commit more than one token")
        print_table(
            f"speculative decoding (gamma={GAMMA}, ragged workload, "
            "self-draft)",
            ["kv_cache", "draft", "verifies", "draft disp", "acc/step",
             "acc rate", "tok/s"],
            spec_rows)

    # the same workloads on the paper's cloud hardware (analytical)
    full = registry.get_config(MODEL)
    sim_rows = []
    sim_kinds = ("aligned", "ragged", "mixed") if scheduler == "chunked" \
        else ("aligned", "ragged")
    for kind in sim_kinds:
        lens = _workload(kind, np.random.default_rng(0))[:MAX_BATCH]
        cap = MIXED_SEQ if kind == "mixed" else MAX_SEQ
        chunk = MIXED_CHUNK if kind == "mixed" else CHUNK
        for kv in ("contiguous", "paged"):
            for hw in (HW.PIM_AI_CHIP, HW.DGX_H100):
                sim = LLMSimulator(full, hw, SimConfig())
                # max_seq_len mirrors the engine's provisioned capacity:
                # the dense charge is max_batch x max_seq_len regardless
                # of what the workload touches
                r = sim.serve(lens, N_NEW, kv_cache=kv,
                              max_seq_len=cap, scheduler=scheduler,
                              chunk_tokens=chunk, gamma=GAMMA,
                              acceptance=0.8)
                sim_rows.append([kind, kv, hw.name, r3(r["tokens_per_s"]),
                                 r3(r["energy_per_token_j"] * 1e3),
                                 r["prefill_chunks"],
                                 f"{r['resident_kv_bytes'] / 2**20:.0f}M",
                                 f"{r['contiguous_kv_bytes'] / 2**20:.0f}M"])
                results["analytical"].append(
                    {"workload": kind, "kv_cache": kv, "profile": hw.name,
                     "scheduler": r["scheduler"],
                     "tokens_per_s": r["tokens_per_s"],
                     "energy_per_token_j": r["energy_per_token_j"],
                     "prefill_chunks": r["prefill_chunks"],
                     "ttft_s": r["ttft_s"],
                     "resident_kv_bytes": r["resident_kv_bytes"],
                     "contiguous_kv_bytes": r["contiguous_kv_bytes"]})
                if scheduler == "chunked":
                    # schedule-shape cross-check: the analytical model
                    # must chunk exactly like the engine's scheduler
                    import math as _m
                    want = sum(_m.ceil(int(n) / chunk) for n in lens)
                    if r["prefill_chunks"] != want:
                        mismatched.append(
                            f"sim schedule shape {kind}/{kv}/{hw.name}: "
                            f"{r['prefill_chunks']} chunks != {want}")
                if scheduler == "speculative":
                    # at 0.8 acceptance the analytical commit rate must
                    # exceed one token per target weight stream
                    if r["accepted_tokens_per_step"] <= 1.0:
                        mismatched.append(
                            f"sim speculative {kind}/{kv}/{hw.name}: "
                            f"{r['accepted_tokens_per_step']:.2f} "
                            "accepted tokens/step <= 1.0")
    print_table(
        f"analytical continuous batching (Table-1 profiles, "
        f"{scheduler} scheduler)",
        ["workload", "kv_cache", "profile", "tok/s", "mJ/token", "chunks",
         "resident KV", "dense KV"],
        sim_rows)

    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2, default=float)
        print(f"\n[wrote {json_path}]")
    if mismatched:
        # hard-fail (CI smoke step must go red on the core invariants)
        raise SystemExit(
            f"serving invariants violated: {mismatched}")
    return results


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write machine-readable results to this path")
    ap.add_argument("--scheduler", default="blocking",
                    choices=["blocking", "chunked", "speculative"],
                    help="scheduling policy for the engine runs (chunked "
                         "also runs the head-of-line comparison; "
                         "speculative also runs the draft/verify "
                         "acceptance gate)")
    ap.add_argument("--cluster", action="store_true",
                    help="run the disaggregated prefill/decode cluster "
                         "benchmark instead: bitwise + fault-injection "
                         "migration gates, plus the analytical "
                         "heterogeneous xPU+PIM TCO scenario")
    ap.add_argument("--trace", default=None,
                    choices=["overload", "steady", "diurnal", "mixshift"],
                    help="replay this seeded multi-tenant trace instead: "
                         "FIFO vs SLO-aware scheduling with bitwise, "
                         "SLO-attainment and throughput gates, the "
                         "analytical schedule mirror, and cloud pricing")
    ap.add_argument("--prefix", action="store_true",
                    help="run the prefix-cache benchmark instead: warm "
                         "vs cold replay of the shared-preamble trace "
                         "with bitwise-output, p99-TTFT, dispatch-audit, "
                         "mirror-exactness and affinity-routing gates, "
                         "plus the hit-rate TCO sweep")
    ap.add_argument("--telemetry", action="store_true",
                    help="run the telemetry benchmark instead: every "
                         "scheduler on both KV backends under one "
                         "shared Telemetry hub, hard-gating bitwise "
                         "outputs, clean dispatch audits, a span "
                         "with its work for every dispatch, every "
                         "required dispatch kind run, "
                         "healthy histograms and a schema-valid "
                         "Perfetto export; writes <json>-trace.json "
                         "and <json>-metrics.prom artifacts")
    ap.add_argument("--mesh", default=None, metavar="D,M",
                    help="run the mesh-sharded engine benchmark instead: "
                         "one engine on a (data, model) device mesh "
                         "(e.g. --mesh 2,4 on an 8-device world) with "
                         "bitwise-output, single-dispatch, audit and "
                         "KV-partition gates, plus the analytical "
                         "mirror and the run_cloud_mesh scaling sweep")
    args = ap.parse_args()
    mesh_arg = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh_arg = (d, m)
    run(args.json, scheduler=args.scheduler, cluster=args.cluster,
        trace=args.trace, prefix=args.prefix, mesh=mesh_arg,
        telemetry=args.telemetry)
