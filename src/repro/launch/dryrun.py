import os

if __name__ == "__main__":
    # Placeholder-pod world ONLY when run as a script (`python -m
    # repro.launch.dryrun`, including the --all subprocess driver).
    # Importers (tests, roofline, scripts) bring their own device
    # count — an unconditional set here would clobber e.g. the
    # 8-device test worlds before their jax import. The placeholders are
    # CPU devices: pinned explicitly, so neither this process nor the
    # per-cell children it starts ever reach for an attached TPU.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices stand in for 2 TPU v5e pods, and
``jax.jit(step).lower(...).compile()`` must succeed for every cell.
``memory_analysis()`` (per-device bytes) proves the cell fits;
``cost_analysis()`` + the HLO collective parse feed §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k \
      --mesh single --out results/dryrun
  python -m repro.launch.dryrun --all [--mesh both] [--skip-done]

``--all`` drives one subprocess per cell (isolation against OOM/compile
failures) and appends JSONL records to ``results/dryrun.jsonl``.
"""
import argparse
import json
import sys
import time
from functools import partial

import jax

from repro.configs import registry
from repro.configs.base import SHAPES


def _collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in an HLO dump."""
    import re

    dt_bytes = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    out = {k: 0 for k in kinds}
    counts = {k: 0 for k in kinds}
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        lhs, rhs = s.split(" = ", 1)
        head = rhs.split("(", 1)[0].strip()
        if not head:
            continue
        # head is "<shape> <opname>", e.g. "f32[8,128]{1,0} all-reduce.1"
        opname = head.split()[-1]
        base = opname.split(".")[0]
        for k in kinds:
            if base == k or base == k + "-start":
                total = 0
                for m in shape_re.finditer(rhs.split("(", 1)[0]):
                    dt, dims = m.group(1), m.group(2)
                    if dt not in dt_bytes:
                        continue
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    total += n * dt_bytes[dt]
                out[k] += total
                counts[k] += 1
                break
    out_nonzero = {k: v for k, v in out.items() if counts[k]}
    return {"bytes": out_nonzero,
            "counts": {k: v for k, v in counts.items() if v},
            "total_bytes": sum(out.values())}


def _mem_dict(mem) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes", "peak_memory_in_bytes")
    return {k: int(getattr(mem, k)) for k in keys}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             collect_hlo: bool = True, overrides: dict | None = None,
             shard_flags: dict | None = None) -> dict:
    from repro.distributed import hints
    from repro.distributed import sharding as SH
    from repro.launch import steps as ST
    from repro.launch.mesh import make_production_mesh
    from repro.models import model as MD
    from repro.optim import AdamW, OptConfig
    import contextlib

    cfg = registry.get_config(arch)
    if overrides:  # §Perf hillclimb variants
        cfg = cfg.replace(**overrides)
    if cfg.moe_expert_shard:  # per-arch override of the module default
        SH.MOE_EXPERT_SHARD = cfg.moe_expert_shard
    for k, v in (shard_flags or {}).items():
        setattr(SH, k, v)
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "devices": int(len(mesh.devices.flat))}
    t0 = time.time()
    ctx = contextlib.ExitStack()
    ctx.enter_context(hints.use_mesh(mesh))

    key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32)  # unused in eval_shape
    params_shape = jax.eval_shape(
        partial(MD.init_params, cfg=cfg), jax.random.PRNGKey(0))
    # §Perf D2: inference cells use serve-mode weight sharding (weights
    # replicated over the FSDP axes when the model fits the HBM budget)
    p_sh = SH.param_shardings(mesh, params_shape,
                              serve=(spec.kind != "train"))

    if spec.kind == "train":
        opt = AdamW(OptConfig(moment_dtype=cfg.optimizer_state_dtype))
        opt_shape = jax.eval_shape(opt.init, params_shape)
        o_sh = SH.opt_state_shardings(mesh, opt_shape)
        batch = MD.batch_spec(cfg, spec.global_batch, spec.seq_len, "train")
        b_sh = SH.batch_shardings(mesh, batch)
        step = ST.build_train_step(cfg, opt)
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_shape, opt_shape, batch)
    elif spec.kind == "prefill":
        batch = MD.batch_spec(cfg, spec.global_batch, spec.seq_len,
                              "prefill")
        b_sh = SH.batch_shardings(mesh, batch)
        cache_shape = MD.cache_spec(cfg, spec.global_batch, spec.seq_len)
        c_sh = SH.cache_shardings(mesh, cache_shape, cfg)
        step = ST.build_prefill_step(cfg, capacity=spec.seq_len)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                         out_shardings=(None, c_sh))
        lowered = jitted.lower(params_shape, batch)
    else:  # decode
        tokens = MD.batch_spec(cfg, spec.global_batch, 1, "decode")["tokens"]
        t_sh = SH.batch_shardings(mesh, tokens)
        cache_shape = MD.cache_spec(cfg, spec.global_batch, spec.seq_len)
        c_sh = SH.cache_shardings(mesh, cache_shape, cfg)
        step = ST.build_serve_step(cfg)
        jitted = jax.jit(step, in_shardings=(p_sh, t_sh, c_sh),
                         out_shardings=(t_sh, None, c_sh),
                         donate_argnums=(2,))
        lowered = jitted.lower(params_shape, tokens, cache_shape)

    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    rec["memory"] = _mem_dict(mem)
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    rec["flops"] = float(cost.get("flops", 0.0))
    rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
    if collect_hlo:
        # trip-count-aware collective volume (scan bodies multiplied)
        from repro.roofline.hlo import collective_bytes
        rec["collectives"] = collective_bytes(compiled.as_text())
    # trip-count-aware GLOBAL flops/bytes from the jaxpr tracer
    # (compiled cost_analysis counts while bodies once — see DESIGN.md)
    try:
        from repro.core import trace as TR
        if spec.kind == "train":
            t_ops = TR.trace_ops(step, params_shape, opt_shape, batch)
        elif spec.kind == "prefill":
            t_ops = TR.trace_ops(step, params_shape, batch)
        else:
            t_ops = TR.trace_ops(step, params_shape, tokens, cache_shape)
        tt = TR.totals(t_ops)
        rec["trace"] = {
            "flops": tt.flops, "matmul_flops": tt.matmul_flops,
            "vector_ops": tt.vector_ops, "bytes": tt.bytes,
            "weight_bytes": tt.weight_bytes,
        }
    except Exception as e:  # noqa: BLE001
        rec["trace_error"] = f"{type(e).__name__}: {e}"
    rec["params"] = int(cfg.param_count())
    rec["active_params"] = int(cfg.active_param_count())
    if overrides:
        rec["overrides"] = overrides
    if shard_flags:
        rec["shard_flags"] = shard_flags
    rec["ok"] = True
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (hillclimb variants)")
    ap.add_argument("--shard", action="append", default=[],
                    help="sharding-module flag key=value")
    ap.add_argument("--tag", default=None,
                    help="variant tag recorded in the output record")
    args = ap.parse_args(argv)

    def _parse_kv(pairs):
        out = {}
        for kv in pairs:
            k, v = kv.split("=", 1)
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
            out[k] = v
        return out

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if not args.all:
        rec = run_cell(args.arch, args.shape, meshes[0],
                       collect_hlo=not args.no_hlo,
                       overrides=_parse_kv(args.set),
                       shard_flags=_parse_kv(args.shard))
        if args.tag:
            rec["tag"] = args.tag
        print(json.dumps(rec, indent=2))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        return 0

    # driver mode: one subprocess per cell for isolation
    import subprocess

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("ok"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    cells = registry.cells()
    total = len(cells) * len(meshes)
    i = 0
    failures = []
    for mesh_kind in meshes:
        for arch, shape in cells:
            i += 1
            if (arch, shape, mesh_kind) in done:
                print(f"[{i}/{total}] skip {arch} {shape} {mesh_kind}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                   "--out", args.out]
            if args.no_hlo:
                cmd.append("--no-hlo")
            t0 = time.time()
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=args.timeout)
                ok = r.returncode == 0
                err = r.stderr[-2000:] if not ok else ""
            except subprocess.TimeoutExpired:
                ok, err = False, f"timeout after {args.timeout}s"
            dt = time.time() - t0
            print(f"[{i}/{total}] {'ok  ' if ok else 'FAIL'} {arch} "
                  f"{shape} {mesh_kind} ({dt:.0f}s)")
            if not ok:
                failures.append((arch, shape, mesh_kind))
                with open(args.out, "a") as f:
                    f.write(json.dumps({
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "ok": False, "error": err}) + "\n")
    if failures:
        print(f"{len(failures)} failures: {failures}")
        return 1
    print("all cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
