"""Find the knee of an open-loop cell: the highest arrival rate the
engine sustains with no growing backlog. Run once, on the chip, when a
cell's rate is chosen; the cell's traffic file then records the knee
and the rate (0.8 x knee), and ``PERF.md`` the sweep.

    python3 -m chipbench.sweep --workload phi3-chat-paged \\
        --rates 1,2,3,4 --seconds 30 --seed 1

One process draws the weights once and, for each rate, builds a fresh
engine, warms it, and serves the cell's mix at that rate. Each rate
prints one JSON line: offered and completed output tokens per second,
TTFT percentiles, the median TTFT of the first and the last quarter of
the window's arrivals (a backlog that grows makes the last quarter
wait longer), the mean backlog (requests due and still without a first
token) over the first and the last third of the window, and the
requests due in the window still without a first token when it closed. There is no drain: the sweep reads the backlog at
the window's close.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from chipbench import drive, families, loadgen, run, system
    system.import_program()
    cell, c, mix, _, _ = run.load_cell(args.workload)
    if mix["loop"] != "open":
        print("chipbench.sweep: only an open-loop mix has a rate",
              file=sys.stderr)
        return 2
    import jax
    run.configure_cache()
    if jax.devices()[0].platform != "tpu":
        print("chipbench.sweep: JAX found no TPU", file=sys.stderr)
        return 2
    family = families.load(c)
    weights = family.make_weights(c, args.seed, dtype=c["torch_dtype"])
    e = c["engine"]
    mean_out = float(np.mean(loadgen.lengths(mix["output"], 4096,
                                             np.random.default_rng(0),
                                             loadgen.STRATA)))
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_rps=rate, drain_cap_s=0.0)
        eng = system.make_engine(family.program_params(weights), c)
        run.warm(eng, m, int(c["vocab_size"]), int(e["max_batch"]))
        plan = loadgen.make_plan(m, args.seed, args.seconds,
                                 int(c["vocab_size"]), int(e["max_seq_len"]))
        rec = drive.serve(eng, plan, ramp_s=float(m["ramp_s"]),
                          seconds=args.seconds,
                          drain_cap_s=float(m["drain_cap_s"]))
        due = rec.due_in_window()
        ttft = [s.stamps[0] - s.due for s in due if s.stamps]
        q = max(1, len(due) // 4)
        first = [s.stamps[0] - s.due for s in due[:q] if s.stamps]
        last = [s.stamps[0] - s.due for s in due[-q:] if s.stamps]
        out = sum(1 for s in rec.sent for t in s.stamps
                  if rec.w0 <= t < rec.w1)
        # backlog: requests due and still without a first token, sampled
        # at 30 instants of the window
        ts = rec.w0 + (np.arange(30) + 0.5) / 30 * (rec.w1 - rec.w0)
        backlog = [sum(1 for s in rec.sent if s.due <= t and
                       (not s.stamps or s.stamps[0] > t)) for t in ts]
        print(json.dumps({
            "rate_rps": rate, "requests_due": len(due),
            "offered_tok_s": rate * mean_out,
            "output_tok_s": out / (rec.w1 - rec.w0),
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else None,
            "ttft_first_quarter_s": float(np.median(first)) if first
            else None,
            "ttft_last_quarter_s": float(np.median(last)) if last else None,
            "backlog_first_third": float(np.mean(backlog[:10])),
            "backlog_last_third": float(np.mean(backlog[-10:])),
            "unserved_at_close": sum(1 for s in due
                                     if not s.stamps or s.stamps[0] >=
                                     rec.w1)}), flush=True)
        del eng, rec, plan
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
