"""Jaxpr op-stream tracer — the measurement substrate of the cost model.

The paper's simulator overrides PyTorch layers/functions and classifies
each call (GEMM / GEMV / activation / normalization), charging time and
energy against a hardware profile. Here we walk the **jaxpr** of the
real JAX graphs instead: every ``dot_general`` becomes a GEMM/GEMV
record, elementwise/reduction primitives become vector-ops records,
gather/scatter/dynamic-slice become data-movement records, and
``pallas_call`` kernels are priced from the inside — the kernel-interior
jaxpr is classified like any other graph, multiplied through the grid,
and the kernel's memory traffic is derived from its BlockSpecs (one
block DMA per grid step along every grid axis the block's index map
actually depends on, plus the scalar-prefetch operands). Control flow
(``scan`` / ``while`` / ``cond`` / ``pjit`` / ``remat``) is recursed
into with trip counts multiplied through — which also makes this tracer
the source of truth for roofline FLOPs/bytes (XLA's ``cost_analysis``
counts loop bodies exactly once).

This module is the bottom layer of the repo's static-analysis cost
model:

- ``trace_ops`` / ``trace_linear`` (here) turn a closure into an op
  stream / a per-op linear model in the cache length;
- :mod:`repro.core.costmodel` applies them to the serving engine's
  *actual jitted closures* (decode step, prefill chunk, verify window,
  bucketed prefill) and audits the engine's dispatch log against the
  priced graphs;
- :class:`repro.core.simulator.LLMSimulator` charges the resulting op
  streams against a :class:`~repro.core.profiles.HardwareProfile`.

``trace_linear`` traces a cache-length-parameterized closure at two
lengths and fits per-op linear models ``cost(L) = a + b*L`` — the
paper's "KV reads grow with every decode iteration" rule, recovered
from real traced graphs instead of hand math.

Known approximations are *surfaced*, never silent: a ``while`` body's
trip count is unknown statically, so it is charged for exactly one
iteration, every record from it is tagged ``approx="while:1-iter"``,
``totals().approx_ops`` counts such records, and a
:class:`TraceUndercountWarning` is emitted at trace time.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field, replace
from functools import partial

import jax
import jax.extend.core as jex_core
import numpy as np

# primitive classification ---------------------------------------------------

MATMUL_PRIMS = {"dot_general"}
CONV_PRIMS = {"conv_general_dilated"}
# elementwise / transcendental — one op per output element
ELEMENTWISE_PRIMS = {
    "add", "sub", "mul", "div", "max", "min", "pow", "exp", "log", "tanh",
    "logistic", "rsqrt", "sqrt", "erf", "abs", "neg", "sign", "floor",
    "ceil", "round", "cos", "sin", "integer_pow", "select_n", "clamp",
    "and", "or", "not", "xor", "rem", "nextafter", "cbrt", "expm1",
    "log1p", "square", "atan2", "exp2",
}
# comparison / bookkeeping — negligible compute, no memory charge
CHEAP_PRIMS = {
    "eq", "ne", "lt", "le", "gt", "ge", "convert_element_type",
    "broadcast_in_dim", "reshape", "transpose", "rev", "iota", "squeeze",
    "expand_dims", "bitcast_convert_type", "is_finite", "stop_gradient",
    "copy", "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "reduce_precision", "real", "imag",
    # pallas-interior bookkeeping: grid position and VMEM/SMEM ref
    # access — on-chip, never main-memory traffic (the kernel's HBM
    # traffic is derived from its BlockSpecs in _pallas_record)
    "program_id", "num_programs", "get", "swap", "addupdate",
    # GSPMD layout metadata, not compute: a hint jaxpr carries these
    # when the closure was first traced under hints.use_mesh (jax
    # caches inner traces by (fn, avals), not by the hint contextvar).
    # Local cost is zero; the implied collective traffic is priced by
    # the simulator's interconnect term, never from the jaxpr.
    "sharding_constraint",
}
REDUCE_PRIMS = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                "reduce_and", "reduce_or", "argmax", "argmin",
                "reduce_window_sum", "reduce_window_max", "cumsum",
                "cummax", "cumlogsumexp", "cumprod"}
DATA_PRIMS = {"gather", "scatter", "scatter-add", "scatter_add",
              "dynamic_slice", "dynamic_update_slice", "concatenate",
              "pad", "slice", "split", "sort", "top_k", "take",
              "rng_bit_generator", "select_and_scatter_add"}
CALL_PRIMS = {"pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
              "custom_vjp_call_jaxpr", "core_call", "remat_call", "remat",
              "checkpoint", "named_call", "custom_transpose_call",
              "shard_map"}


class TraceUndercountWarning(UserWarning):
    """A traced graph contains a construct whose cost is statically
    unknowable (e.g. a ``while`` loop's trip count) and was charged at
    a declared approximation. The affected records carry ``approx`` and
    are counted by ``totals().approx_ops`` — undercounted loops are
    visible, not invisible."""


@dataclass
class OpRecord:
    """One traced operation (already multiplied by loop trip counts)."""
    kind: str          # gemm|gemv|conv|elementwise|reduce|data|kernel|other
    prim: str
    flops: float = 0.0       # multiply-accumulate*2 for matmuls
    in_bytes: float = 0.0    # operand bytes
    out_bytes: float = 0.0
    weight_bytes: float = 0.0  # bytes of the rank-2 (weight) operand
    rows: int = 0            # GEMM row count (tokens) — GEMV when <= 1
    count: float = 1.0       # trip-count multiplier applied
    batch_dims: int = 0      # dot_general batch-dim count (attention
                             # scores GEMMs have >= 2: B and H)
    kernel: str = ""         # pallas kernel name (kind == "kernel")
    approx: str = ""         # non-empty: cost is a declared guess
                             # (e.g. "while:1-iter")

    def scaled(self, m: float) -> "OpRecord":
        return replace(self, flops=self.flops * m,
                       in_bytes=self.in_bytes * m,
                       out_bytes=self.out_bytes * m,
                       weight_bytes=self.weight_bytes * m,
                       count=self.count * m)


def _aval_bytes(aval) -> float:
    try:
        return float(np.prod(aval.shape, dtype=np.float64)
                     * np.dtype(aval.dtype).itemsize)
    except Exception:  # noqa: BLE001 — abstract tokens etc.
        return 0.0


def _dot_record(eqn) -> OpRecord:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dims = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dims
    batch = int(np.prod([lhs.shape[i] for i in lb], dtype=np.int64)) or 1
    contract = int(np.prod([lhs.shape[i] for i in lc], dtype=np.int64)) or 1
    m = int(np.prod([lhs.shape[i] for i in range(len(lhs.shape))
                     if i not in lc and i not in lb], dtype=np.int64)) or 1
    n = int(np.prod([rhs.shape[i] for i in range(len(rhs.shape))
                     if i not in rc and i not in rb], dtype=np.int64)) or 1
    flops = 2.0 * batch * m * n * contract
    in_b = _aval_bytes(lhs) + _aval_bytes(rhs)
    out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    # the rank-2 operand with no batch dims is (heuristically) the weight
    weight_b = 0.0
    for a, bdims in ((lhs, lb), (rhs, rb)):
        if len(a.shape) == 2 and not bdims:
            weight_b = max(weight_b, _aval_bytes(a))
    # stacked weights (MoE experts (E,d,f), sLSTM recurrent (H,p,q)):
    # rank-3 RHS under a single batch dim — einsum convention puts the
    # parameter second throughout the model zoo.
    if weight_b == 0.0 and len(lb) == 1 and len(rhs.shape) == 3:
        weight_b = _aval_bytes(rhs)
    rows = m if len(lhs.shape) - len(lb) - len(lc) > 0 else 1
    kind = "gemv" if m * batch <= max(batch, 1) or m == 1 else "gemm"
    # batched GEMV (decode): m==1 per batch element
    if m == 1:
        kind = "gemv"
    return OpRecord(kind, "dot_general", flops, in_b, out_b, weight_b,
                    rows=m * batch, batch_dims=len(lb))


def _conv_record(eqn) -> OpRecord:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    k_elems = int(np.prod(rhs.shape, dtype=np.int64))
    out_elems = int(np.prod(out.shape, dtype=np.int64))
    # flops = 2 * out_spatial*batch * (k elements per output channel)
    cout = rhs.shape[eqn.params["dimension_numbers"].rhs_spec[0]] \
        if hasattr(eqn.params.get("dimension_numbers"), "rhs_spec") \
        else rhs.shape[-1]
    flops = 2.0 * out_elems * max(1, k_elems // max(1, cout))
    return OpRecord("conv", "conv", flops,
                    _aval_bytes(lhs) + _aval_bytes(rhs),
                    _aval_bytes(out), _aval_bytes(rhs))


# pallas_call ---------------------------------------------------------------

def _index_map_grid_deps(index_map_jaxpr, n_grid: int) -> list:
    """Which of the leading ``n_grid`` invars (the grid indices) of a
    BlockSpec index map reach its outputs. Backward reachability over
    the jaxpr — purely structural, no concrete grid values needed, so
    it also handles maps that dereference scalar-prefetch operands
    (paged block tables: ``tab[b, w]`` depends on grid axes b and w
    *through* the table)."""
    jaxpr = index_map_jaxpr.jaxpr
    needed = {v for v in jaxpr.outvars if isinstance(v, jex_core.Var)}
    for eqn in reversed(jaxpr.eqns):
        if any(v in needed for v in eqn.outvars):
            needed.update(v for v in eqn.invars
                          if isinstance(v, jex_core.Var))
    return [jaxpr.invars[i] in needed
            for i in range(min(n_grid, len(jaxpr.invars)))]


def _block_mapping_bytes(bm, grid) -> float:
    """HBM traffic of one pallas operand across the whole grid: the
    block is DMA'd once per grid step along every axis its index map
    depends on, and stays resident (no re-fetch) along axes it is
    invariant to — e.g. the split-KV decode kernel streams each KV tile
    exactly once while its q / output blocks are fetched once per
    (batch, head), not per KV block."""
    elems = int(np.prod(bm.transformed_block_aval.shape, dtype=np.int64))
    itemsize = np.dtype(bm.array_aval.dtype).itemsize
    deps = _index_map_grid_deps(bm.index_map_jaxpr, len(grid))
    fetches = 1
    for axis, dep in enumerate(deps):
        if dep:
            fetches *= int(grid[axis])
    return float(elems * itemsize * fetches)


def _pallas_record(eqn) -> OpRecord:
    """Price a ``pallas_call`` from the inside: classify the
    kernel-interior jaxpr (FLOPs per grid step — VMEM-local byte
    records like ``get``/``swap`` are on-chip and discarded), multiply
    through the grid, and derive HBM bytes from the BlockSpecs plus the
    scalar-prefetch operands. Falls back to an operand-bytes "other"
    record only when the grid is dynamic (not statically priceable)."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(gm.grid)
    name = (eqn.params["name"]
            or eqn.params["jaxpr"].debug_info.func_name)
    if not all(isinstance(d, (int, np.integer)) for d in grid):
        return OpRecord(
            "other", "pallas_call", 0.0,
            sum(_aval_bytes(v.aval) for v in eqn.invars),
            sum(_aval_bytes(v.aval) for v in eqn.outvars), kernel=name)
    trips = int(np.prod(grid, dtype=np.int64)) if grid else 1
    interior: list = []
    _walk(eqn.params["jaxpr"], 1.0, interior)
    flops = sum(o.flops for o in interior) * trips
    mm = [o for o in interior if o.kind in ("gemm", "gemv", "conv")]
    rows = max((o.rows for o in mm), default=0)
    # memory traffic: scalar-prefetch operands land whole (SMEM), block
    # operands stream per the BlockSpec fetch model above
    n_pref = int(gm.num_index_operands)
    in_b = sum(_aval_bytes(v.aval) for v in eqn.invars[:n_pref])
    n_in = int(gm.num_inputs)
    for bm in gm.block_mappings[:n_in]:
        in_b += _block_mapping_bytes(bm, grid)
    out_b = sum(_block_mapping_bytes(bm, grid)
                for bm in gm.block_mappings[n_in:])
    return OpRecord("kernel", "pallas_call", float(flops), in_b, out_b,
                    rows=rows, count=trips, kernel=name)


def _branch_cost(records) -> tuple:
    return (sum(o.flops for o in records),
            sum(o.in_bytes + o.out_bytes for o in records))


def _walk(jaxpr, mult: float, out: list):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in MATMUL_PRIMS:
            out.append(_dot_record(eqn).scaled(mult))
        elif name in CONV_PRIMS:
            out.append(_conv_record(eqn).scaled(mult))
        elif name == "pallas_call":
            out.append(_pallas_record(eqn).scaled(mult))
        elif name == "scan":
            # ``unroll`` is a lowering hint only: the traced jaxpr keeps
            # the full ``length`` and a single body copy regardless of
            # the unroll factor (verified by test_scan_unroll_is_a_
            # lowering_hint), so the trip multiplier is exactly length.
            length = eqn.params["length"]
            inner = eqn.params["jaxpr"]
            _walk(inner.jaxpr, mult * length, out)
        elif name == "while":
            # trip count unknown statically: charge exactly one
            # iteration, tag every record from the body, and say so —
            # undercounted loops must be visible (totals().approx_ops).
            body: list = []
            _walk(eqn.params["body_jaxpr"].jaxpr, mult, body)
            warnings.warn(TraceUndercountWarning(
                f"while loop charged for 1 iteration ({len(body)} ops; "
                "trip count is not static) — totals().approx_ops counts "
                "the affected records"), stacklevel=3)
            out.extend(replace(o, approx="while:1-iter") for o in body)
        elif name == "cond":
            # charge the most expensive branch (worst case): pl.when
            # bodies, checkpoint policies etc. put the compute in one
            # branch and a no-op in the other
            walked = []
            for br in eqn.params["branches"]:
                recs: list = []
                _walk(br.jaxpr, mult, recs)
                walked.append(recs)
            if walked:
                out.extend(max(walked, key=_branch_cost))
        elif name in CALL_PRIMS or "jaxpr" in eqn.params or \
                "call_jaxpr" in eqn.params:
            sub = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            if sub is None:
                continue
            sub = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            _walk(sub, mult, out)
        elif name in ELEMENTWISE_PRIMS:
            elems = sum(int(np.prod(v.aval.shape, dtype=np.int64))
                        for v in eqn.outvars)
            out.append(OpRecord(
                "elementwise", name, float(elems),
                sum(_aval_bytes(v.aval) for v in eqn.invars),
                sum(_aval_bytes(v.aval) for v in eqn.outvars)).scaled(mult))
        elif name in REDUCE_PRIMS or name.startswith("reduce"):
            elems = sum(int(np.prod(v.aval.shape, dtype=np.int64))
                        for v in eqn.invars)
            out.append(OpRecord(
                "reduce", name, float(elems),
                sum(_aval_bytes(v.aval) for v in eqn.invars),
                sum(_aval_bytes(v.aval) for v in eqn.outvars)).scaled(mult))
        elif name in DATA_PRIMS:
            in_sizes = [_aval_bytes(v.aval) for v in eqn.invars]
            out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            if name in ("gather", "take", "dynamic_slice", "top_k", "sort"):
                # reads only the gathered rows, not the whole table
                in_b = sum(in_sizes) - (max(in_sizes) if in_sizes else 0)
                out_b = out_b
            elif name in ("dynamic_update_slice", "scatter", "scatter_add",
                          "scatter-add", "select_and_scatter_add"):
                # writes only the update slice, not the whole base buffer
                in_b = sum(in_sizes) - (max(in_sizes) if in_sizes else 0)
                out_b = in_b
            else:
                in_b = sum(in_sizes)
            out.append(OpRecord(
                "data", name, 0.0, in_b, out_b).scaled(mult))
        elif name in CHEAP_PRIMS:
            continue
        else:
            # unknown primitive: record bytes, no flops — the lint gate
            # (scripts/lint_prims.py) fails when one of these carries
            # real traffic, so new primitives get classified instead of
            # silently dropping out of the cost model
            out.append(OpRecord(
                "other", name, 0.0,
                sum(_aval_bytes(v.aval) for v in eqn.invars),
                sum(_aval_bytes(v.aval) for v in eqn.outvars)).scaled(mult))


def trace_ops(fn, *specs, **kw) -> list:
    """Trace ``fn(*specs)`` (ShapeDtypeStructs ok) into OpRecords."""
    jaxpr = jax.make_jaxpr(fn)(*specs, **kw)
    out: list = []
    _walk(jaxpr.jaxpr, 1.0, out)
    return out


@dataclass
class Totals:
    flops: float = 0.0
    matmul_flops: float = 0.0
    vector_ops: float = 0.0
    bytes: float = 0.0
    weight_bytes: float = 0.0
    gemm_flops: float = 0.0
    gemv_flops: float = 0.0
    kernel_flops: float = 0.0  # share of matmul_flops inside pallas calls
    approx_ops: int = 0        # records carrying a declared approximation
                               # (while bodies charged at 1 iteration)


def totals(ops) -> Totals:
    t = Totals()
    for o in ops:
        t.flops += o.flops
        t.bytes += o.in_bytes + o.out_bytes
        t.weight_bytes += o.weight_bytes
        if o.approx:
            t.approx_ops += 1
        if o.kind in ("gemm", "gemv", "conv"):
            t.matmul_flops += o.flops
            if o.kind == "gemv":
                t.gemv_flops += o.flops
            else:
                t.gemm_flops += o.flops
        elif o.kind == "kernel":
            # hand-tiled kernels are matmul-class compute; keep the
            # GEMM/GEMV split by the interior row count (decode-style
            # kernels with one query row per head group stay GEMV-like)
            t.matmul_flops += o.flops
            t.kernel_flops += o.flops
            if o.rows > 1:
                t.gemm_flops += o.flops
            else:
                t.gemv_flops += o.flops
        else:
            t.vector_ops += o.flops
    return t


# ---------------------------------------------------------------------------
# two-point linear tracing (KV growth)
# ---------------------------------------------------------------------------

@dataclass
class LinearOp:
    """cost(L) = const + slope * L, per field."""
    kind: str
    prim: str
    flops: tuple = (0.0, 0.0)
    in_bytes: tuple = (0.0, 0.0)
    out_bytes: tuple = (0.0, 0.0)
    weight_bytes: tuple = (0.0, 0.0)
    batch_dims: int = 0
    rows: int = 0
    kernel: str = ""
    approx: str = ""

    def at(self, L: float) -> OpRecord:
        ev = lambda c: c[0] + c[1] * L  # noqa: E731
        return OpRecord(self.kind, self.prim, ev(self.flops),
                        ev(self.in_bytes), ev(self.out_bytes),
                        ev(self.weight_bytes), batch_dims=self.batch_dims,
                        rows=self.rows, kernel=self.kernel,
                        approx=self.approx)


def trace_linear(fn_of_len, L1: int, L2: int) -> list:
    """``fn_of_len(L)`` must return (fn, specs) for cache length L with an
    identical code path; ops are matched positionally and fit linearly."""
    f1, s1 = fn_of_len(L1)
    f2, s2 = fn_of_len(L2)
    ops1 = trace_ops(f1, *s1)
    ops2 = trace_ops(f2, *s2)
    if len(ops1) != len(ops2):
        raise ValueError(
            f"op streams differ ({len(ops1)} vs {len(ops2)}); cache length "
            "must not change the traced code path")
    out = []
    dL = float(L2 - L1)
    for a, b in zip(ops1, ops2):
        if a.prim != b.prim or a.kernel != b.kernel:
            raise ValueError(
                f"op mismatch: {a.prim}{a.kernel and f'[{a.kernel}]'} vs "
                f"{b.prim}{b.kernel and f'[{b.kernel}]'}")

        def fit(x, y):
            slope = (y - x) / dL
            return (x - slope * L1, slope)

        out.append(LinearOp(a.kind, a.prim,
                            fit(a.flops, b.flops),
                            fit(a.in_bytes, b.in_bytes),
                            fit(a.out_bytes, b.out_bytes),
                            fit(a.weight_bytes, b.weight_bytes),
                            batch_dims=a.batch_dims, rows=a.rows,
                            kernel=a.kernel, approx=a.approx))
    return out
