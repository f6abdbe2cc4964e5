"""Static-analysis cost model over the serving engine's real dispatch
graphs, plus the simulator<->engine drift audit.

Before this layer existed, :class:`~repro.core.simulator.LLMSimulator`
hand-mirrored every engine feature (ragged decode, chunked prefill,
speculative verify, paged caches) with its own ``MD.*`` trace
constructions — five PRs of mirrors, each a drift liability. Now the
pricing and the engine share one source of truth:
:func:`repro.serving.engine.build_closures` returns the engine's
dispatch graphs as plain functions, the engine ``jax.jit``'s them, and
:class:`DispatchPricer` ``jax.make_jaxpr``'s them through
:mod:`repro.core.trace`. A new kernel, family, or cache backend is
priced automatically the moment the engine can dispatch it.

Two halves:

- :class:`DispatchPricer` — memoized traced op streams for each
  dispatch kind (bucketed prefill, ragged decode, prefill chunk,
  speculative verify), with decode/verify fitted linear in the cache
  length via :func:`~repro.core.trace.trace_linear`. The simulator's
  ``_decode_ops_linear`` / ``_prefill_ops`` / ``_chunk_ops`` /
  ``_verify_ops_linear`` delegate here (and alias the memo dicts).
- :func:`audit_engine` — the drift gate. A :class:`~repro.serving.
  engine.ServingEngine` records every jitted dispatch in
  ``dispatch_log`` (step index, kind, operand spec tree); the audit
  re-traces each entry through the engine's own closures and fails on:
  an **unpriced dispatch** (no closure for the kind, or the trace
  errors), an **unknown primitive** classified ``"other"`` above a
  bytes threshold (the cost model would silently drop its traffic), an
  **op-stream mismatch** between the engine's decode/verify graph and
  the one the simulator prices, or a **one-target-dispatch-per-step
  invariant violation**. ``assert_no_drift`` raises on any of these —
  that is the CI gate (tests/test_costmodel.py).
"""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp

from repro.core import trace as T
from repro.models import model as MD
from repro.serving.engine import build_closures

# target-model dispatch kinds (the per-step invariant applies to these;
# draft_* kinds are the speculative scheduler's small-model calls)
TARGET_STEP_KINDS = ("decode", "verify")


def params_spec(cfg):
    """ShapeDtypeStruct tree of a model's parameters (no allocation)."""
    return jax.eval_shape(lambda k: MD.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def _fit_window(max_len: int) -> tuple:
    """Two cache lengths bracketing ``max_len`` for the linear fit."""
    L1 = max(32, max_len // 2)
    L2 = max_len
    if L1 == L2:  # degenerate fit window (max_len == 32)
        L1 = max(1, L2 // 2)
    return L1, L2


class DispatchPricer:
    """Traced op streams for every engine dispatch kind, memoized.

    The closures being traced are the module-level
    ``engine.build_closures`` functions — the same objects the engine
    jits — so whatever graph the engine dispatches is, byte for byte,
    the graph being priced. Memo dicts are public: the simulator
    aliases them (``LLMSimulator._decode_linear`` *is*
    ``pricer.decode_linear``), keeping its memoization-regression tests
    meaningful.
    """

    def __init__(self, cfg):
        self.cfg = cfg
        self.decode_linear = {}   # (batch, max_len, ragged, kv, bs)
        self.prefill_cache = {}   # (batch, n_in)
        self.chunk_cache = {}     # (chunk_tokens, capacity, kind)
        self.verify_linear = {}   # (batch, max_len, gamma, kv, bs)
        self._params = None

    def _params_spec(self):
        if self._params is None:
            self._params = params_spec(self.cfg)
        return self._params

    # -- dispatch kinds ----------------------------------------------------
    def prefill_ops(self, batch: int, n_in: int):
        """One bucketed whole-prompt prefill dispatch (``n_in`` tokens
        into an ``n_in``-capacity cache — per-request encode cost is
        independent of the serving engine's configured capacity)."""
        key = (batch, n_in)
        if key not in self.prefill_cache:
            fn = build_closures(self.cfg, n_in)["prefill"]
            spec = MD.batch_spec(self.cfg, batch, n_in, "prefill")
            idx = jax.ShapeDtypeStruct((), jnp.int32)
            self.prefill_cache[key] = T.trace_ops(
                fn, self._params_spec(), spec, idx, idx)
        return self.prefill_cache[key]

    def decode_ops_linear(self, batch: int, max_len: int, *,
                          ragged: bool = False,
                          kv_cache: str = "contiguous",
                          kv_block_size: int = 16):
        """Linear-in-cache-length op stream of one decode step.

        ``ragged=True`` traces the engine's actual single-dispatch
        ragged closure (per-row position vector + live mask);
        ``kv_cache="paged"`` feeds it the block-table cache view — KV
        pools sized to the *resident* worst case — so simulated cloud
        batching charges the same compiled graph, and the same resident
        KV bytes, as the engine backend it models. ``ragged=False`` is
        the aligned single-sequence graph (``MD.decode_step`` without a
        live mask) that the engine never dispatches but
        ``LLMSimulator.decode``'s historical API charges. Memoized per
        key — a reused pricer must never return the first call's trace
        for a different batch size or sequence length."""
        key = (batch, max_len, ragged, kv_cache, kv_block_size)
        if key not in self.decode_linear:
            params = self._params_spec()
            dec = build_closures(self.cfg, max_len)["decode"]

            def of_len(L):
                if kv_cache == "paged":
                    cache = MD.paged_cache_spec(
                        self.cfg, batch, L, kv_block_size, ragged=ragged)
                else:
                    cache = MD.cache_spec(self.cfg, batch, L)
                tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
                if ragged:
                    cache["len"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
                    vec = jax.ShapeDtypeStruct((batch,), jnp.int32)
                    live = jax.ShapeDtypeStruct((batch,), jnp.bool_)
                    return dec, (params, tok, cache, vec, live)

                def fn(p, t, c):
                    return MD.decode_step(p, self.cfg, t, c)

                return fn, (params, tok, cache)

            self.decode_linear[key] = T.trace_linear(
                of_len, *_fit_window(max_len))
        return self.decode_linear[key]

    def verify_ops_linear(self, batch: int, max_len: int, gamma: int, *,
                          kv_cache: str = "contiguous",
                          kv_block_size: int = 16):
        """Linear-in-cache-length op stream of one speculative verify
        dispatch: ``gamma + 1`` candidate tokens per row against the
        row's cached history — the engine's ragged ``verify`` closure,
        traced at two cache lengths exactly like the decode step so the
        cost model stays honest to the streamed-KV growth."""
        key = (batch, max_len, gamma, kv_cache, kv_block_size)
        if key not in self.verify_linear:
            params = self._params_spec()
            ver = build_closures(self.cfg, max_len)["verify"]

            def of_len(L):
                if kv_cache == "paged":
                    cache = MD.paged_cache_spec(
                        self.cfg, batch, L, kv_block_size, ragged=True)
                else:
                    cache = MD.cache_spec(self.cfg, batch, L)
                cache["len"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
                tok = jax.ShapeDtypeStruct((batch, gamma + 1), jnp.int32)
                vec = jax.ShapeDtypeStruct((batch,), jnp.int32)
                live = jax.ShapeDtypeStruct((batch,), jnp.bool_)
                return ver, (params, tok, cache, vec, live)

            self.verify_linear[key] = T.trace_linear(
                of_len, *_fit_window(max_len))
        return self.verify_linear[key]

    def chunk_ops(self, chunk_tokens: int, capacity: int,
                  kind: str = "contiguous", kv_block_size: int = 16):
        """Traced op stream of one chunked-prefill dispatch over a
        one-slot cache of the full ``capacity``: the engine closure
        slices (contiguous) or block-gathers (paged) the slot's history
        inside the jit and masks it by ``hist_len``, so per-chunk cost
        is constant in the history length — honest to the
        implementation, not a hand model."""
        key = (chunk_tokens, capacity, kind, kv_block_size)
        if key not in self.chunk_cache:
            cfg = self.cfg
            fn = build_closures(cfg, capacity)[f"chunk_{kind}"]
            batch = {"tokens": jax.ShapeDtypeStruct((1, chunk_tokens),
                                                    jnp.int32)}
            st = MD.cache_struct(cfg, 1, capacity)
            kshape, kdtype = st["k"]
            if kind == "paged":
                # one slot's resident worst case: W = ceil(cap/bs)
                # blocks in the pool and in the block table
                bs = kv_block_size
                w = -(-capacity // bs)
                pool = jax.ShapeDtypeStruct(
                    (kshape[0], w, bs, *kshape[3:]), kdtype)
                kh, vh = pool, pool
                sel = jax.ShapeDtypeStruct((w,), jnp.int32)
            else:
                kh = jax.ShapeDtypeStruct(*st["k"])
                vh = jax.ShapeDtypeStruct(*st["v"])
                sel = jax.ShapeDtypeStruct((), jnp.int32)
            hist = jax.ShapeDtypeStruct((), jnp.int32)
            idx = jax.ShapeDtypeStruct((), jnp.int32)
            self.chunk_cache[key] = T.trace_ops(
                fn, self._params_spec(), batch, kh, vh, sel, hist, idx)
        return self.chunk_cache[key]


# ---------------------------------------------------------------------------
# dispatch audit: engine log -> priced graphs, or fail
# ---------------------------------------------------------------------------

def _spec_tree(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        if hasattr(x, "shape") and hasattr(x, "dtype") else x, tree)


def entry_tracer(engine):
    """Memoized ``dispatch_log`` entry -> traced op stream for one engine.

    This is the exact join the drift audit uses: ``kind`` selects the
    engine's own jitted closure (``draft_*`` kinds route to the draft
    model's closures and params), and the entry's operand spec tree is
    re-traced through it, so what :func:`audit_engine` checks is the
    graph the engine dispatched under that entry's
    ``engine.dispatch.<kind>`` span. The device time of each dispatch
    is measured from a ``jax.profiler`` trace of those spans and their
    programs (``repro.serving.telemetry``), never by blocking on it.
    Raises ``KeyError`` for a kind with no closure.
    """
    closures = engine._closures
    draft_closures = getattr(engine, "_draft_closures", None)
    pspec = _spec_tree(engine.params)
    dspec = (_spec_tree(engine.draft_params)
             if getattr(engine, "draft_params", None) is not None else None)
    traced = {}  # (kind, spec repr) -> op stream, traced once

    def trace_entry(entry):
        kind = entry["kind"]
        if kind.startswith("draft_"):
            fn = (draft_closures or {}).get(kind[len("draft_"):])
            ps = dspec
        else:
            fn = closures.get(kind)
            ps = pspec
        if fn is None or ps is None:
            raise KeyError(f"no closure for dispatch kind {kind!r}")
        key = (kind, repr(entry["spec"]))
        if key not in traced:
            traced[key] = T.trace_ops(fn, ps, *entry["spec"])
        return traced[key]

    return trace_entry


def audit_engine(engine, *, other_bytes_threshold: float = 4096.0) -> dict:
    """Map every dispatch an engine actually issued to a priced graph.

    Re-traces each ``engine.dispatch_log`` entry through the engine's
    own ``build_closures`` functions (the objects it jitted) and
    returns a report dict; ``report["ok"]`` is False on any drift:

    - ``unpriced``: a dispatch kind with no closure, or whose re-trace
      fails — the simulator cannot price what the engine ran;
    - ``unknown_prims``: a primitive the tracer classifies ``"other"``
      carrying more than ``other_bytes_threshold`` bytes — its traffic
      would silently vanish from the cost model;
    - ``zero_flop_kernels``: a ``pallas_call`` that priced to zero
      FLOPs — the kernel-interior descent failed;
    - ``stream_mismatch``: the engine's decode/verify op stream differs
      positionally from the stream :class:`DispatchPricer` prices for
      the same (batch, kv backend) — simulator-vs-engine drift;
    - ``invariant_violations``: a step with more than one target-model
      dispatch (the one-dispatch-per-step invariant, checked
      structurally from the log rather than from counters).
    """
    log = engine.dispatch_log
    report = {
        "dispatches": len(log), "priced": 0, "kinds": Counter(),
        "unpriced": [], "unknown_prims": [], "zero_flop_kernels": [],
        "stream_mismatch": [], "invariant_violations": [],
    }
    trace_entry = entry_tracer(engine)

    seen_streams = set()
    pricer = DispatchPricer(engine.cfg)
    kv_kind = "paged" if "paged" in engine.kv.name else "contiguous"
    bs = engine.ecfg.kv_block_size
    for entry in log:
        kind = entry["kind"]
        report["kinds"][kind] += 1
        try:
            ops = trace_entry(entry)
        except Exception as e:  # noqa: BLE001 — the audit must report,
            report["unpriced"].append(          # not crash, on bad kinds
                {"step": entry["step"], "kind": kind, "error": repr(e)})
            continue
        report["priced"] += 1
        for o in ops:
            if (o.kind == "other"
                    and o.in_bytes + o.out_bytes > other_bytes_threshold):
                report["unknown_prims"].append(
                    {"kind": kind, "prim": o.prim,
                     "bytes": o.in_bytes + o.out_bytes})
            if o.prim == "pallas_call" and o.flops == 0 and o.count > 0:
                report["zero_flop_kernels"].append(
                    {"kind": kind, "kernel": o.kernel})
        # decode/verify: the engine stream must equal the stream the
        # simulator prices for the same shape class, op for op
        if kind in TARGET_STEP_KINDS:
            toks = entry["spec"][0]
            batch = int(toks.shape[0])
            skey = (kind, batch, int(toks.shape[1]))
            if skey in seen_streams:
                continue
            seen_streams.add(skey)
            if kind == "decode":
                model = pricer.decode_ops_linear(
                    batch, engine.ecfg.max_seq_len, ragged=True,
                    kv_cache=kv_kind, kv_block_size=bs)
            else:
                model = pricer.verify_ops_linear(
                    batch, engine.ecfg.max_seq_len,
                    int(toks.shape[1]) - 1,
                    kv_cache=kv_kind, kv_block_size=bs)
            got = [o.prim for o in ops]
            want = [o.prim for o in model]
            if got != want:
                report["stream_mismatch"].append(
                    {"kind": kind, "batch": batch,
                     "engine_ops": len(got), "model_ops": len(want)})
    per_step = Counter(e["step"] for e in log
                       if e["kind"] in TARGET_STEP_KINDS)
    report["invariant_violations"] = sorted(
        s for s, c in per_step.items() if c > 1)
    report["ok"] = not (report["unpriced"] or report["unknown_prims"]
                        or report["zero_flop_kernels"]
                        or report["stream_mismatch"]
                        or report["invariant_violations"])
    return report


def assert_no_drift(report: dict):
    """Raise AssertionError with a readable summary unless the audit
    came back clean — the callable form of the CI drift gate."""
    if report.get("ok"):
        return
    lines = [f"dispatch audit failed "
             f"({report['priced']}/{report['dispatches']} priced):"]
    for k in ("unpriced", "unknown_prims", "zero_flop_kernels",
              "stream_mismatch"):
        for item in report[k]:
            lines.append(f"  {k}: {item}")
    if report["invariant_violations"]:
        lines.append(f"  >1 target dispatch at steps "
                     f"{report['invariant_violations']}")
    raise AssertionError("\n".join(lines))
