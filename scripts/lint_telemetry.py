"""CI lint: every engine dispatch must ride the telemetry wrapper.

Two checks, both fatal (nonzero exit):

1. **Static (AST)** — in ``serving/engine.py``, every call to
   ``self._log_dispatch`` must occur inside ``ServingEngine._dispatch``.
   ``_dispatch`` is the single site that logs the dispatch and opens
   its ``engine.dispatch.<kind>`` span (a profiler annotation when
   telemetry is on); a bare ``_log_dispatch`` call anywhere else is a
   dispatch the span tracer and the device trace would silently miss.

2. **Runtime** — drive mini engines (blocking / chunked / speculative,
   both KV backends split across them) with a live ``Telemetry`` hub
   and require that (a) the engine made dispatches, and (b) every
   logged kind has its dispatch span: the ``engine.dispatch.<kind>``
   spans on that engine's track are, in order, exactly its
   ``dispatch_log`` — the kinds the cost model prices are the kinds
   the device trace names.

Usage: python scripts/lint_telemetry.py [--skip-runtime]
"""
from __future__ import annotations

import argparse
import ast
import pathlib
import sys

ENGINE_PY = (pathlib.Path(__file__).resolve().parent.parent
             / "src" / "repro" / "serving" / "engine.py")
MODEL = "qwen1.5-0.5b"


def _enclosing_function(tree: ast.AST):
    """Map every node to the name of its nearest enclosing function."""
    owner = {}

    def walk(node, fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn = node.name
        for child in ast.iter_child_nodes(node):
            owner[child] = fn
            walk(child, fn)

    walk(tree, None)
    return owner


def lint_static() -> list[str]:
    tree = ast.parse(ENGINE_PY.read_text(), filename=str(ENGINE_PY))
    owner = _enclosing_function(tree)
    problems = []
    sites = 0
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "_log_dispatch"
                and isinstance(f.value, ast.Name)
                and f.value.id == "self"):
            continue
        sites += 1
        fn = owner.get(node)
        if fn != "_dispatch":
            problems.append(
                f"engine.py:{node.lineno}: self._log_dispatch called "
                f"from {fn!r} — dispatches must go through _dispatch so "
                "the span tracer and profiler see them")
    if sites == 0:
        problems.append("engine.py: no _log_dispatch call sites found — "
                        "lint is looking at the wrong seam")
    return problems


def lint_runtime() -> list[str]:
    import jax
    import numpy as np

    from repro.configs import registry
    from repro.models import model as MD
    from repro.serving import EngineConfig, ServingEngine, Telemetry

    cfg = registry.get_smoke_config(MODEL).replace(dtype="float32")
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    tel = Telemetry()
    flavors = [
        ("blocking", dict(kv_cache="contiguous", scheduler="blocking")),
        ("chunked", dict(kv_cache="paged", scheduler="chunked",
                         chunk_tokens=16)),
        ("speculative", dict(kv_cache="contiguous",
                             scheduler="speculative", spec_gamma=2)),
    ]
    problems = []
    for label, kw in flavors:
        eng = ServingEngine(params, cfg, EngineConfig(
            max_batch=2, max_seq_len=64, max_new_tokens=3, **kw),
            telemetry=tel, telemetry_label=label)
        for n in (5, 9):
            eng.submit(rng.integers(0, cfg.vocab_size, size=n))
        eng.run()
        logged = [f"engine.dispatch.{e['kind']}" for e in eng.dispatch_log]
        spanned = [s.name for s in sorted(tel.tracer.spans,
                                          key=lambda s: s.index)
                   if s.tid == label and s.cat == "dispatch"]
        if not logged:
            problems.append(f"{label}: engine made no dispatches — "
                            "workload too small to lint")
        if spanned != logged:
            missing = sorted(set(logged) - set(spanned))
            problems.append(
                f"{label}: {len(spanned)} dispatch spans for "
                f"{len(logged)} dispatch-log entries (kinds never "
                f"spanned: {missing})")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--skip-runtime", action="store_true",
                    help="AST check only (no JAX, sub-second)")
    args = ap.parse_args(argv)
    failed = 0
    for label, check in (("static", lint_static),
                         ("runtime", None if args.skip_runtime
                          else lint_runtime)):
        if check is None:
            print(f"SKIP {label}")
            continue
        try:
            problems = check()
        except Exception as e:  # noqa: BLE001 — a check that won't run
            problems = [f"check failed: {type(e).__name__}: {e}"]
        if problems:
            failed += 1
            for p in problems:
                print(f"FAIL {label:8s} {p}")
        else:
            print(f"OK   {label}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
