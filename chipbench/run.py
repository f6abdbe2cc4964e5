"""Run one benchmark cell once, on the chips of this machine.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration file, its traffic mix and its metrics are
found by name from ``BENCHMARK.json``, and what the cell itself sets
(``chipbench/cells/<cell>.json``, where there is one) is merged over its
configuration. The run draws the weights on the device from the seed
(the configuration's family, ``chipbench/families``), builds the
serving engine the configuration names (or a cluster of one worker per
chip), warms every prompt shape the mix can draw, then sends the mix's
requests when they are due and steps the engine: a ramp, the measured
window of ``--seconds``, and a drain. With ``--trace 0`` it reports the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from
a profiler trace of a steady stretch, taken with the program's own
telemetry on and programs compiled afresh. Then it frees the engine and
decides ``correct`` against the float32 reference
(``chipbench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checked``: each number compared, beside its
limit. Without a TPU, or with fewer chips than the cell asks for, the
run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCH = ROOT / "BENCHMARK.json"
CELLS_DIR = HERE / "cells"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"
TRACE_S = 10.0          # traced stretch, unless the mix sets trace_s
WARM_GRID_MAX = 64      # prompt lengths warmed one by one up to this many
# recorded for every program JAX compiles or loads from its persistent
# cache: none may be fetched inside the window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_cell(name: str, bench_path: Path = BENCH):
    """(cell, configuration, traffic mix, end-to-end names, per-layer
    names) of workload ``name``. The cell's own file, where there is
    one, may hold ``engine`` (``EngineConfig`` fields merged over the
    configuration's) and ``cluster`` (``{"n_prefill", "n_decode"}``:
    serve with a ``ClusterEngine`` of one worker per chip)."""
    from chipbench import loadgen
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    c = json.loads((ROOT / conf["file"]).read_text())
    own = CELLS_DIR / f"{name}.json"
    if own.is_file():
        own = json.loads(own.read_text())
        c["engine"] = {**c["engine"], **own.get("engine", {})}
        if "cluster" in own:
            c["cluster"] = own["cluster"]
    mix = loadgen.load_mix(cell["traffic"])

    def names(group):
        return [m["name"] for m in bench[group]
                if name in m.get("workloads", [name])]
    return cell, c, mix, names("end_to_end"), names("per_layer")


def metric_reader(name: str):
    """``read(record)`` of ``chipbench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def warm_lengths(spec: dict) -> list:
    """Prompt lengths that reach every prefill shape the mix can draw:
    each length of its grid where the grid is small, else its ends and
    every power of two between them (one per power-of-two bucket)."""
    q = int(spec.get("quantum", 1))
    lo, hi = int(spec["min"]), int(spec["max"])
    grid = list(range(-(-lo // q) * q, hi + 1, q))
    if len(grid) <= WARM_GRID_MAX:
        return grid
    out, p = {lo, hi}, 1
    while p < hi:
        if p > lo:
            out.add(p)
        p *= 2
    return sorted(out)


def warm(eng, mix: dict, vocab: int, max_batch: int, plan=None) -> int:
    """Serve one short request per warm length, a batch at a time, so
    every program the window uses is compiled or loaded. A cluster
    hands a prompt's KV to one decode worker, each on its own chip, and
    compiles the handoff's gather and splice for each count of KV blocks,
    so there the warm lengths are every prompt length of ``plan`` (the
    run's plan), and each is served once per decode worker: that many
    prompts of a length go in together and the router, least loaded
    first, gives each worker one. A handoff's splice into a pool that no
    decode has returned yet compiles apart from one into a pool a decode
    returned, as in the window, so a cluster first serves one request
    on each decode worker. Returns the number of warm requests."""
    import numpy as np
    rng = np.random.default_rng(0)
    lens = warm_lengths(mix["prompt"])
    copies = len(getattr(eng, "decode_workers", [eng]))
    if copies > 1:
        lens = sorted({len(p) for p in plan.prompts})
    batches = [lens[i:i + max_batch] for i in range(0, len(lens),
                                                     max_batch)]
    if copies > 1:
        batches.insert(0, lens[:1])
    for batch in batches:
        for n in batch:
            for _ in range(copies):
                eng.submit(rng.integers(0, vocab, n, dtype=np.int32),
                           max_new_tokens=3)
        eng.run()
    return sum(map(len, batches)) * copies


def configure_cache(trace: bool = False) -> None:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at a fixed directory in the
    checkout; every program is kept, however quickly it compiled. A
    traced run compiles its own programs: the cache's key leaves out the
    operations' names, so a program loaded from it may carry the layer
    scopes of another tree (``spans.py``)."""
    import jax
    if trace:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _device_record(devs) -> dict:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks),
            "memory_peak_bytes_per_chip": peaks}


def run(c: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
        metric_names: list, t_start: float = T_START, devices=None,
        base_arch=None, fault=None, control: bool = False,
        log=print) -> dict:
    """One run of a cell with configuration ``c`` and traffic ``mix``.
    ``fault`` (tests only) is called with the warmed engine to break the
    timed path; ``control`` also puts the fp8 control in the program's
    place on the same requests and reports its own verdict under
    ``control``."""
    import jax

    from chipbench import check, drive, families, loadgen, peaks, system
    from chipbench import spans as SP
    from chipbench import trace as TR
    from chipbench import work as W

    devs = list(devices or jax.devices()[:1])
    try:
        pk = peaks.peaks_for(devs[0].device_kind)
    except KeyError:
        if devs[0].platform == "tpu":
            raise
        pk = None           # no published peaks for a CPU test run
    family = families.load(c)
    needed = families.Needed(family, c)
    e = c["engine"]
    compiles = [0]

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    # set-up in parts: start-up (imports, backend), weights, engine, warm-up
    marks = [time.perf_counter()]
    weights = jax.block_until_ready(
        family.make_weights(c, seed, dtype=c["torch_dtype"]))
    marks.append(time.perf_counter())
    eng = system.make_engine(family.program_params(weights), c, base_arch,
                             telemetry=trace, devices=devs)
    marks.append(time.perf_counter())
    plan = loadgen.make_plan(mix, seed, seconds, int(c["vocab_size"]),
                             int(e["max_seq_len"]))
    n_warm = warm(eng, mix, int(c["vocab_size"]), int(e["max_batch"]),
                  plan)
    marks.append(time.perf_counter())
    setup_s = marks[-1] - t_start
    parts = " ".join(f"{n} {b - a:.2f}" for n, a, b in zip(
        ("start-up", "weights", "engine", "warm-up"), [t_start] + marks,
        marks))
    log(f"set-up {setup_s:.2f} s: {parts} ({n_warm} warm requests, "
        f"{compiles[0]} programs compiled or loaded)", file=sys.stderr)
    if fault is not None:
        fault(eng)
    tracer = watch = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = drive.Tracer(TRACE_DIR,
                              min(float(mix.get("trace_s", TRACE_S)), seconds))
        watch = system.Watch(eng, needed)
    before = compiles[0]
    rec = drive.serve(eng, plan, ramp_s=float(mix["ramp_s"]),
                      seconds=seconds, drain_cap_s=float(mix["drain_cap_s"]),
                      tracer=tracer, watch=watch)
    in_window = compiles[0] - before
    records = watch.records if watch is not None and watch.enabled else None
    watch = None        # its wrappers hold the engine
    rec.setup_s, rec.peaks, rec.chips = setup_s, pk, len(devs)
    rec.needed = needed
    device = _device_record(devs)
    finished = {id(r) for r in eng.finished}
    done = [s for s in rec.sent if id(s.req) in finished]
    short = sum(len(s.req.output) != s.n_out for s in done)
    del eng
    gc.collect()

    breakdown = None
    if trace:
        events = SP.extract(TR.find_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        red = TR.reduce(events)
        rec.trace, rec.spans = red, SP.reduce(events, needed.scopes)
        del events
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        if records is not None:
            kinds = {"decode": "decode", "prefill": "prefill",
                     "chunk": "chunk_" + system.engine_config(c).kv_cache}
            work: dict = {}
            for step, kind, w in records:
                if tracer.first_step <= step < tracer.last_step:
                    work.setdefault(kinds[kind], W.Work())
                    work[kinds[kind]] += w
            rec.stretch_work = work
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        log(json.dumps({"programs": red["programs"],
                        "dispatch": rec.spans["dispatch"]}),
            file=sys.stderr)

    metrics = {}
    bench = json.loads(BENCH.read_text()) if BENCH.is_file() else None
    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer")
             for m in (bench or {}).get(g, [])}
    for name in metric_names:
        value = metric_reader(name)(rec)
        if value is not None:
            metrics[name] = {"value": value, "unit": units.get(name, "")}

    length = min(int(e["max_seq_len"]),
                 int(mix["prompt"]["max"]) + int(mix["output"]["max"]))
    picked = check.sample(done, seed, int(c["check"]["sample_requests"]))
    t0 = time.perf_counter()
    rows = check.reference_gaps(weights, c, picked, length, family,
                                control=control)
    log(f"reference over {len(rows)} requests, "
        f"{sum(r['served'] for r in rows)} served tokens: "
        f"{time.perf_counter() - t0:.2f} s; programs compiled or loaded "
        f"in the window: {in_window}", file=sys.stderr)
    for r in rows:
        log(json.dumps(r), file=sys.stderr)
    limit = float(c["check"]["max_logit_gap"])
    ok, checked = check.verdict(rows, short, limit)
    due = rec.due_in_window()
    out = {"correct": bool(ok), "attempted": len(due),
           "failed": sum(1 for s in due if not s.stamps),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = in_window
    if control:
        # the fp8 control in the program's place, through the same verdict
        c_ok, c_checked = check.verdict(
            [{"gap": r["control_gap"]} for r in rows], short, limit)
        out["control"] = {"correct": c_ok, "checked": c_checked}
    out["checked"] = checked
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import system
    try:
        system.import_program()
        cell, c, mix, e2e, layer = load_cell(args.workload)
    except (OSError, KeyError, RuntimeError, ValueError) as err:
        print(f"chipbench: {err}", file=sys.stderr)
        return 2
    import jax
    configure_cache(trace=bool(args.trace))
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this benchmark runs only on one",
              file=sys.stderr)
        return 2
    if len(devs) < int(cell["chips"]):
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    out = run(c, mix, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), metric_names=layer if args.trace
              else e2e, devices=devs[:int(cell["chips"])])
    for name, v in out["checked"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
