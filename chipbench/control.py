"""Readings that set a cell's ``check.max_logit_gap``, on the chip at the
cell's own size: for each seed, one run of the cell (the cell's traffic
and load, a window of ``--seconds``), then over the same sampled
requests both the program's reading (the widest reference gap of its
served tokens) and the control's (the widest reference gap of the token
an fp8 computation of the reference puts first at each position), each
through ``check.verdict`` against the cell's limit: the program's run
must come out correct and the control's not.

    python3 -m chipbench.control --workload danube-decode-contig \\
        --seeds 101,102,103 --seconds 30

One process runs every seed; each prints one JSON line. The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import run, system
    system.import_program()
    cell, c, mix, e2e, _ = run.load_cell(args.workload)
    import jax
    run.configure_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("chipbench.control: JAX found no TPU", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run(c, mix, seed=seed, seconds=args.seconds, trace=False,
                      metric_names=e2e, t_start=time.perf_counter(),
                      devices=devs[:int(cell["chips"])], control=True)
        ctl = out["control"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program_gap": out["checked"]["max_logit_gap"]["value"],
            "control_gap": ctl["checked"]["max_logit_gap"]["value"],
            "correct": out["correct"], "control_correct": ctl["correct"],
            "metrics": out["metrics"], "device": out["device"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
