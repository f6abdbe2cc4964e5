"""Three-term roofline analysis of the dry-run artifacts (§Roofline).

For every (arch x shape x mesh) cell recorded by ``repro.launch.dryrun``
we derive, against TPU v5e hardware constants:

  compute term    = HLO_FLOPs / (chips x 197 TFLOP/s bf16)
  memory term     = HLO_bytes / (chips x 819 GB/s HBM)
  collective term = collective_bytes / link (50 GB/s ICI per link)

Sources: both leading terms come from the jaxpr tracer
(``core/trace.py``), which multiplies ``scan``/``while`` trip counts
through and prices ``pallas_call`` kernels from their BlockSpecs —
unlike XLA's ``cost_analysis``, which counts loop bodies exactly once:

  - compute_s  = trace.flops / chips / PEAK  (trip-aware, global)
  - memory_s   = trace.bytes / chips / HBM_BW when the record's trace
    carries byte totals (``launch/dryrun.py`` writes them). Records
    from before the tracer reported bytes fall back to
    cost.bytes_accessed * kappa / HBM_BW, where kappa =
    (trace.flops / chips) / cost.flops is the measured trip multiplier
    of this executable (flops and HBM bytes scale with the same loop
    structure); with no trace at all, kappa = 1.
  - collective_s = hlo-parsed per-device payload bytes / LINK_BW (the
    parser multiplies while-loop trip counts through; see
    roofline/hlo.py).

Derived qualities:
  - bottleneck: argmax of the three terms.
  - MODEL_FLOPS: 6·N_active·D (train) or 2·N_active·D (prefill/decode),
    D = processed tokens; the ratio MODEL_FLOPS/HLO_FLOPs exposes
    remat/redundancy overhead.
  - roofline_frac: useful-model-FLOPs MFU at the bound =
    (MODEL_FLOPS/chips/PEAK) / max(term) — the number §Perf hillclimbs.
"""
from __future__ import annotations

import json
import os


# TPU v5e per-chip constants (assignment-specified)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
LINK_BW = 50e9           # bytes/s per ICI link

DEFAULT_RESULTS = os.path.join("results", "dryrun.jsonl")

SHAPE_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,       # one token per sequence
    "long_500k": 1,
}
TRAIN_SHAPES = {"train_4k"}


def model_flops(rec: dict) -> float:
    n_active = rec.get("active_params") or rec.get("params") or 0
    tokens = SHAPE_TOKENS.get(rec["shape"], 0)
    mult = 6.0 if rec["shape"] in TRAIN_SHAPES else 2.0
    return mult * n_active * tokens


def analyze_record(rec: dict) -> dict:
    chips = rec.get("devices", 256)
    cost_flops = rec.get("flops", 0.0) or 1.0
    trace = rec.get("trace") or {}
    g_flops = trace.get("flops") or cost_flops * chips
    kappa = (g_flops / chips) / cost_flops if cost_flops else 1.0
    compute_s = g_flops / chips / PEAK_FLOPS
    t_bytes = trace.get("bytes") or 0.0
    if t_bytes:
        # trip-aware global bytes straight from the jaxpr tracer
        memory_s = t_bytes / chips / HBM_BW
    else:
        memory_s = rec.get("bytes_accessed", 0.0) * kappa / HBM_BW
    coll = (rec.get("collectives") or {}).get("total_bytes", 0)
    collective_s = coll / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(rec)
    bound_s = max(terms.values()) or 1.0
    out = {
        "arch": rec["arch"], "shape": rec["shape"],
        "mesh": rec.get("mesh", "single"), "chips": chips,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "bottleneck": bottleneck,
        "kappa": kappa,
        "hlo_flops_global": g_flops,
        "model_flops": mf,
        "model_flops_ratio": mf / g_flops if g_flops else 0.0,
        "roofline_frac": (mf / chips / PEAK_FLOPS) / bound_s,
        "peak_bytes_per_chip": int(
            (rec.get("memory") or {}).get("peak_memory_in_bytes", 0)),
    }
    return out


def load_records(path: str = DEFAULT_RESULTS, mesh: str | None = None
                 ) -> list[dict]:
    """Latest record per (arch, shape, mesh) cell."""
    latest: dict[tuple, dict] = {}
    with open(path) as f:
        for line in f:
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not r.get("ok"):
                continue
            latest[(r["arch"], r["shape"], r.get("mesh", "single"))] = r
    recs = [r for k, r in sorted(latest.items())
            if mesh is None or k[2] == mesh]
    return recs


def analyze_file(path: str = DEFAULT_RESULTS, mesh: str | None = "single"
                 ) -> list[dict]:
    return [analyze_record(r) for r in load_records(path, mesh)]


def advice(cell: dict) -> str:
    """One sentence on what would move the dominant term down."""
    b = cell["bottleneck"]
    if b == "compute":
        if cell["model_flops_ratio"] < 0.4:
            return ("compute-bound with low useful/HLO ratio: relax the "
                    "remat policy (checkpoint dots) to stop recompute "
                    "dominating")
        return ("compute-bound near the useful-FLOP floor: only larger "
                "per-chip batch or lower-precision matmuls move this")
    if b == "memory":
        return ("memory-bound: raise arithmetic intensity — larger batch "
                "per chip, fuse KV/weight streams (flash/decode kernels), "
                "or quantize the streamed weights")
    return ("collective-bound: reshard to cut the dominant collective "
            "(FSDP all-gather <-> TP all-reduce trade), overlap "
            "collectives with compute, or compress gradients")


def to_markdown(cells: list[dict]) -> str:
    lines = [
        "| arch | shape | mesh | compute_s | memory_s | collective_s | "
        "bound | 6ND/HLO | roofline | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for c in cells:
        lines.append(
            f"| {c['arch']} | {c['shape']} | {c['mesh']} | "
            f"{c['compute_s']:.3e} | {c['memory_s']:.3e} | "
            f"{c['collective_s']:.3e} | {c['bottleneck']} | "
            f"{c['model_flops_ratio']:.2f} | {c['roofline_frac']:.3f} | "
            f"{advice(c)} |")
    return "\n".join(lines)
