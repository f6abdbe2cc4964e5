"""Continuous-batching serving engine over a pluggable KV-cache API.

The paper's cloud scenario batches decode requests "to balance memory
bandwidth and compute performance" (§1.2) and keeps KV state resident
next to the memory that serves it (§3.4). This module is the
framework-side realization: a slot-based continuous-batching engine in
the vLLM style, adapted to JAX's static-shape world, that consumes its
KV cache **only** through the :class:`~repro.serving.kv_cache.
KVCacheManager` protocol:

- ``can_admit(n_prompt, budget)`` gates admission on actual capacity,
- ``splice(rows, slot, ...)`` lands a batch-1 prefill into a slot,
- ``decode_view(pos, live)`` yields the device pytree one ragged
  decode dispatch consumes (dense cache, or block pools + block
  tables),
- ``commit(new_cache)`` stores the dispatch's result,
- ``free(slot)`` releases everything at retirement,
- ``resident_kv_bytes()`` is what the engine (and the analytical
  simulator) report instead of assuming ``max_batch x max_seq_len``.

Two backends ship: ``ContiguousCache`` (dense per-slot rows — the only
layout recurrent families and rolling SWA caches support) and
``PagedCache`` (fixed-size blocks + per-slot block tables + free-list
allocator; blocks allocate lazily and free at retirement, so ragged
workloads hold resident KV strictly below the contiguous footprint and
admission can oversubscribe slots against the same pool). The decode
hot path is identical either way: exactly **one** jitted dispatch per
engine step (``decode_dispatches`` counts them), with per-slot position
and live-mask vectors threaded through ``decode_step`` → ``attn_decode``
→ the split-KV decode kernel — paged caches additionally thread the
block table, which the kernel dereferences via scalar prefetch.

Sampling is a separate head outside the jitted model closures: the
prefill/decode dispatches return logits, and ``EngineConfig.sample``
picks the token — ``"greedy"`` (argmax, bitwise identical to the fused
path it replaced) or ``"temperature"`` (temperature + optional top-k,
per-request seeds folded with the request id and absolute position so a
request's stream is reproducible wherever its slots land).

Prefill admission is *bucketed* for attention families: prompts are
right-padded to a small geometric set of bucket lengths so admission
compiles once per bucket. Pad positions are causally downstream of the
real tokens and their garbage KV is masked off by the per-slot length
vector (paged backends never even store pad blocks past the prompt).
Prompts longer than the capacity are truncated with a warning and the
original length recorded on the request. Retirement is checked at admit
time (a ``max_new_tokens<=1`` budget or an EOS prefill token never
occupies a decode slot; ``max_new_tokens=0`` — an explicit zero, not an
unset field — never even runs prefill) and after each decode step.

*When* prefills run is a policy owned by the :mod:`~repro.serving.
scheduler` subsystem: ``EngineConfig.scheduler`` selects
``"blocking"`` (whole-prompt prefill at admission — the historical
behavior) or ``"chunked"`` (Sarathi-style token-budgeted mixed steps:
every iteration packs decode tokens for all live slots plus at most
one ``chunk_tokens``-sized prefill chunk, chunk *k* attending chunks
``0..k-1`` through the KV cache). The engine keeps the mechanism —
``step`` consults the scheduler for admission, chunk selection, and
retirement, then issues at most one prefill-chunk dispatch and exactly
one ragged decode dispatch. Greedy outputs are bitwise identical
across schedulers; only the *schedule* (TTFT, inter-token latency)
changes. ``Request.ttft_s`` is always measured to the first *sampled*
token — under chunking that is the end of the prompt's final chunk,
and ``Request.prefill_chunks`` counts the chunks it took to get there.

``"speculative"`` (LP-Spec direction) replaces the one-token decode
with a draft/verify loop: a small draft model — an
``EngineConfig.draft`` registry pair sharing the target's vocabulary,
or the ``"self"`` fallback reusing the target's first
``spec_draft_layers`` layers — proposes ``spec_gamma`` tokens per live
slot from its own contiguous shadow cache, and the target verifies the
whole ragged batch of ``(slot, gamma+1)`` candidate windows in **one**
jitted dispatch (``model.verify_tokens``, the multi-token
generalization of the chunked prefill-over-cache attention). The
longest accepted prefix plus one bonus token commit per row, capped by
budget/EOS/capacity in stream order; rejection is rollback by
bookkeeping — host-side lengths stay at the accepted prefix, the next
dispatch overwrites, and paged backends free over-allocated blocks
(``KVCacheManager.commit_n``). Decode is memory-bound (the paper's
mobile argument, §1.2): each verify streams the target's weights once
for up to ``gamma+1`` tokens, so accepted tokens per weight pass — and
energy per token — improve with the acceptance rate. Greedy outputs
remain bitwise identical to vanilla greedy decode (acceptance compares
against the target argmax, so the committed stream *is* the vanilla
stream; exact in float32 — under bf16, ulp noise between the verify
and decode attention summation orders can flip a near-tie argmax);
``Request.spec_accepted`` records per-round commit counts and
``summary()`` reports draft dispatches separately — the
one-target-dispatch-per-step invariant is unchanged.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.distributed import hints
from repro.distributed import sharding as SH
from repro.models import model as MD
from repro.serving.kv_cache import (ContiguousCache, contiguous_kv_bytes,
                                    make_kv_cache)
from repro.serving.scheduler import PrefillState, make_scheduler
from repro.serving.telemetry import NULL_TELEMETRY


# operand index of the KV cache in the decode/verify closures
# ``(params, toks, cache, pos, live)``: the engine donates it, so each
# step's new cache reuses the resident buffer
CACHE_ARGNUM = 2


def build_closures(cfg, capacity: int, *, masked: bool | None = None):
    """The engine's jitted dispatch graphs, as plain functions of
    ``(params, *operands)``, keyed by dispatch kind.

    Module-level on purpose: the static cost model
    (:mod:`repro.core.costmodel`) traces **these same function
    objects** — the engine jits them, the pricer ``make_jaxpr``'s them
    — so the graph the simulator charges and the graph the engine
    dispatches cannot drift apart without the audit noticing.

    ``capacity`` is the KV capacity the prefill graph writes into
    (``EngineConfig.max_seq_len`` in the engine; the prompt length in
    the simulator's per-request encode model). ``masked`` forces the
    length-masked prefill scan (defaults to recurrent families, which
    need pad steps neutralized; attention families keep their exact
    pre-mask graph for bitwise stability)."""
    C = capacity
    if masked is None:
        masked = cfg.family in MD.RECURRENT_FAMILIES

    def prefill(params, batch, last_idx, n_valid):
        """One bucketed whole-prompt (or draft) prefill dispatch."""
        return MD.prefill(params, cfg, batch, C, logit_index=last_idx,
                          length=n_valid if masked else None)

    def decode(params, toks, cache, pos, live):
        """One fully-ragged dispatch: every live slot advances at
        its own absolute position; non-live rows keep their KV and
        recurrent state exactly (masked inside ``decode_step``)."""
        logits, new = MD.decode_step(params, cfg, toks,
                                     dict(cache, len=pos), live=live)
        new["len"] = cache["len"]  # positions tracked host-side
        return logits, new

    def chunk_contiguous(params, batch, cache_k, cache_v, slot, hist_len,
                         logit_idx):
        """One prefill-chunk dispatch over a contiguous cache: the
        slot's dense history rows are sliced inside the jit (no
        host-side copy per chunk)."""
        kh = jax.lax.dynamic_slice_in_dim(cache_k, slot, 1, axis=1)
        vh = jax.lax.dynamic_slice_in_dim(cache_v, slot, 1, axis=1)
        return MD.prefill_chunk(params, cfg, batch, kh, vh, hist_len,
                                logit_index=logit_idx)

    def chunk_paged(params, batch, pool_k, pool_v, table, hist_len,
                    logit_idx):
        """Paged analogue: each layer gathers the slot's pool blocks
        through its block-table row into a dense history view (sentinels
        clamped, garbage blocks masked by ``hist_len``)."""
        return MD.prefill_chunk(params, cfg, batch, pool_k, pool_v,
                                hist_len, logit_index=logit_idx,
                                block_table=table)

    def verify(params, toks, cache, pos, live):
        """One multi-token verify dispatch: every live slot's
        gamma+1 candidate window is checked at its own absolute
        position; candidate KVs land live-masked at per-row
        offsets, rejected positions stay masked by the host-side
        length vector (rollback by bookkeeping, not by rewrite)."""
        logits, new = MD.verify_tokens(params, cfg, toks,
                                       dict(cache, len=pos), live=live)
        new["len"] = cache["len"]  # positions tracked host-side
        return logits, new

    return {"prefill": prefill, "decode": decode,
            "chunk_contiguous": chunk_contiguous,
            "chunk_paged": chunk_paged, "verify": verify}


@dataclass
class EngineConfig:
    max_batch: int = 8           # decode slots
    max_seq_len: int = 2048      # KV positions per request (capacity)
    eos_token: int = -1          # -1 -> never stops on token
    max_new_tokens: int = 64
    sample: str = "greedy"       # "greedy" | "temperature"
    temperature: float = 1.0     # sampling temperature (sample="temperature")
    top_k: int = 0               # 0 -> full vocab
    seed: int = 0                # base sampling seed (per-request override
                                 # via ``submit(..., seed=)``)
    prefill_bucket_min: int = 16  # smallest prompt bucket (power-of-two
                                  # buckets up from here); 0 disables
                                  # bucketing even for attention families
    kv_cache: str = "contiguous"  # "contiguous" | "paged"
    kv_block_size: int = 16       # paged: positions per KV block
    kv_blocks: int = 0            # paged: pool size; 0 -> auto
                                  # (max_batch * max_seq_len / block_size)
    prefix_cache: bool = False    # paged: hash-based prefix caching —
                                  # admissions splice shared immutable
                                  # blocks for the longest cached
                                  # block-aligned prompt prefix and
                                  # prefill only the suffix. Ignored by
                                  # contiguous backends, image-prefix
                                  # (vlm) configs, and the speculative
                                  # policy (the draft's shadow cache
                                  # needs the whole prompt).
    scheduler: str = "blocking"   # "blocking" | "chunked" |
                                  # "speculative" (serving/scheduler.py)
    chunk_tokens: int = 64        # chunked: prompt tokens per prefill
                                  # chunk (one chunk dispatch per step)
    spec_gamma: int = 4           # speculative: draft tokens proposed
                                  # per verify step
    draft: str = "self"           # speculative draft: "self" (reuse the
                                  # target's first k layers) or a
                                  # registry arch id sharing the vocab
    spec_draft_layers: int = 0    # self-draft depth; 0 -> n_layers // 2
                                  # (>= 1); == n_layers makes the draft
                                  # the target (acceptance -> 100%)
    mesh: tuple | None = None     # (data, model): run this engine's
                                  # dispatches on a jax device mesh —
                                  # attention heads / MoE experts
                                  # tensor-parallel over ``model``, the
                                  # KV slot batch over ``data``, via the
                                  # serve-mode sharding rules. Greedy
                                  # streams stay bitwise identical to
                                  # the single-device engine (the
                                  # gather-rows TP layout), and the
                                  # one-dispatch-per-step invariant is
                                  # untouched. None -> default device.

    def __post_init__(self):
        """Reject nonsensical configs with clear errors instead of
        downstream shape/compile failures."""
        if self.max_batch < 1:
            raise ValueError(
                f"max_batch={self.max_batch} must be >= 1 (the engine "
                "needs at least one decode slot)")
        if self.max_seq_len < 2:
            raise ValueError(
                f"max_seq_len={self.max_seq_len} must be >= 2 (one "
                "prompt position plus one decode position)")
        if self.scheduler not in ("blocking", "chunked", "speculative",
                                  "slo"):
            raise ValueError(f"unknown scheduler {self.scheduler!r} "
                             "(expected 'blocking', 'chunked', "
                             "'speculative' or 'slo')")
        if self.scheduler == "speculative":
            if self.spec_gamma < 1:
                raise ValueError(
                    f"spec_gamma={self.spec_gamma} must be >= 1 (at "
                    "least one draft token per verify step)")
            if self.sample != "greedy":
                raise ValueError(
                    "speculative decoding requires sample='greedy': "
                    "longest-accepted-prefix verification is exact only "
                    "against the target argmax (stochastic acceptance "
                    "would need rejection sampling)")
        if self.mesh is not None:
            m = tuple(int(x) for x in self.mesh)
            if len(m) != 2 or any(x < 1 for x in m):
                raise ValueError(
                    f"mesh={self.mesh!r} must be a (data, model) pair "
                    "of positive axis sizes")
            self.mesh = m
        if self.scheduler == "chunked":
            if self.chunk_tokens < 1:
                raise ValueError(
                    f"chunk_tokens={self.chunk_tokens} must be >= 1")
            if (self.prefill_bucket_min > 0
                    and self.chunk_tokens % self.prefill_bucket_min):
                raise ValueError(
                    f"chunk_tokens={self.chunk_tokens} must be a "
                    f"multiple of the prefill bucket quantum "
                    f"(prefill_bucket_min={self.prefill_bucket_min}), "
                    "so chunk shapes stay on the compiled bucket grid")


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int | None = None
    seed: int | None = None            # per-request sampling seed
    # multi-tenant workload attribution (serving/workload.py traces):
    tenant: str = ""                   # tenant name ("" = untagged)
    priority: int = 0                  # higher preempts lower (SLO policy)
    slo: object | None = None          # scheduler.SLO TTFT/ITL targets
    arrival_s: float | None = None     # trace arrival time (virtual clock)
    # filled by the engine:
    output: list = field(default_factory=list)
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    truncated_from: int | None = None  # original prompt length, if clipped
    prefill_chunks: int = 0            # prefill dispatches this request took
    preemptions: int = 0               # times this request was evicted to
                                       # the queue and later resumed
    spec_accepted: list = field(default_factory=list)
    # per-verify-round committed token counts (accepted prefix + bonus,
    # capped by budget/EOS/capacity) — sums to the request's
    # decode-phase tokens, len(output) - 1

    @property
    def ttft_s(self) -> float:
        """Time to the first *sampled* token. Under chunked prefill
        that is the end of the prompt's final chunk — intermediate
        chunks produce no token and must not count as "first token"."""
        return self.t_first - self.t_submit

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    @property
    def itl_s(self) -> float:
        """Mean inter-token latency over the decode phase."""
        n = len(self.output)
        return (self.t_done - self.t_first) / (n - 1) if n > 1 else 0.0

    @property
    def slo_met(self) -> bool:
        """Whether the measured TTFT/ITL hit the request's targets
        (vacuously true without an SLO)."""
        if self.slo is None:
            return True
        return (self.ttft_s <= self.slo.ttft_s + 1e-9
                and self.itl_s <= self.slo.itl_s + 1e-9)


@dataclass
class SlotPacket:
    """Host-side snapshot of one live decode slot: everything needed to
    resume the stream elsewhere — on another worker (cluster drain /
    handoff) or in the same engine later (SLO preemption). ``kv`` is the
    backend-portable ``export_slot`` payload. Because sampling is keyed
    by ``(seed, rid, position)``, resuming from a packet is bitwise
    identical to never having moved."""
    req: Request
    seed: int
    tok: int          # pending input token (last sampled)
    pos: int          # absolute position
    gen_len: int      # tokens generated so far
    n_prompt: int     # prompt length at bind
    budget: int       # generation budget
    kv: dict          # export_slot payload (host arrays + metadata)
    hops: int = 0     # migrations this stream has survived


def request_breakdowns(done) -> dict:
    """Per-tenant and per-priority latency/SLO breakdowns over finished
    requests. Shared by ``ServingEngine.summary`` and
    ``ClusterEngine.summary`` (and reused by the workload replay
    reports), so every reporting surface slices traffic identically."""
    def pct(vals, q):
        return float(np.percentile(vals, q)) if vals else 0.0

    def bucket(key_fn):
        groups: dict = {}
        for r in done:
            groups.setdefault(key_fn(r), []).append(r)
        out = {}
        for k in sorted(groups, key=str):
            rs = groups[k]
            ttft = [r.ttft_s for r in rs]
            itl = [r.itl_s for r in rs if len(r.output) > 1]
            out[k] = {
                "requests": len(rs),
                "ttft_p50_s": pct(ttft, 50),
                "ttft_p99_s": pct(ttft, 99),
                "itl_p50_s": pct(itl, 50),
                "itl_p99_s": pct(itl, 99),
                "preemptions": sum(r.preemptions for r in rs),
                "slo_attainment": sum(r.slo_met for r in rs) / len(rs),
            }
        return out

    return {"by_tenant": bucket(lambda r: r.tenant or "default"),
            "by_priority": bucket(lambda r: r.priority)}


class ServingEngine:
    def __init__(self, params, cfg, ecfg: EngineConfig, *,
                 draft_params=None, draft_cfg=None, devices=None,
                 telemetry=None, telemetry_label: str | None = None):
        self.cfg = cfg
        self.ecfg = ecfg
        # observability: a shared serving.telemetry.Telemetry hub (span
        # tracer + metrics + dispatch profiler). Defaults to the
        # disabled singleton — every hook then short-circuits to a
        # no-op, so the hot path pays one attribute load + branch and
        # outputs stay bitwise identical either way.
        self.telemetry = NULL_TELEMETRY if telemetry is None else telemetry
        self.tel_label = telemetry_label or "engine"
        B, C = ecfg.max_batch, ecfg.max_seq_len
        # tensor/sequence-parallel serving: an ``ecfg.mesh`` of
        # (data, model) places this engine on a device mesh — weights
        # under the serve-mode sharding rules (model-axis only when the
        # model fits the budget, so each ``data`` replica reads local
        # weights), the KV pool batch-over-data / heads-over-model.
        # ``devices`` restricts the mesh to an explicit device group
        # (the cluster hands each worker a disjoint sub-mesh).
        self.mesh = None
        if ecfg.mesh is not None:
            d, m = ecfg.mesh
            devs = list(devices) if devices is not None else jax.devices()
            if len(devs) < d * m:
                raise ValueError(
                    f"mesh={ecfg.mesh} needs {d * m} devices, but only "
                    f"{len(devs)} are "
                    + ("in the worker's device group" if devices
                       is not None else "visible to jax"))
            self.mesh = Mesh(
                np.asarray(devs[:d * m]).reshape(d, m), ("data", "model"))
            params = jax.device_put(
                params,
                SH.param_shardings(
                    self.mesh, jax.eval_shape(lambda: params), serve=True))
        self.params = params
        self.kv = make_kv_cache(cfg, ecfg, mesh=self.mesh)
        # host-side slot bookkeeping
        self.slot_req: list[Request | None] = [None] * B
        self.slot_len = np.zeros(B, np.int32)     # tokens generated
        self.slot_pos = np.zeros(B, np.int32)     # absolute position
        self.slot_tok = np.zeros((B, 1), np.int32)
        self.slot_rid = np.zeros(B, np.int32)     # sampling stream ids
        self.slot_seed = np.zeros(B, np.int32)
        self.slot_nprompt = np.zeros(B, np.int32)  # prompt len at bind
        self.waiting: deque[Request] = deque()
        self.finished: list[Request] = []
        self._next_rid = 0
        # clock: wall by default; trace replay switches to a virtual
        # clock (``set_now``) so TTFT/ITL are measured in deterministic
        # simulated seconds — step-space determinism makes the whole
        # schedule reproducible and exactly mirrorable analytically
        self.clock = "wall"
        self.now_s = 0.0
        # SLO preemption state: rid -> SlotPacket for evicted-but-
        # unfinished streams; admission resumes them from the packet
        self.preempted_packets: dict[int, SlotPacket] = {}
        self.preemptions = 0
        self.preempted_kv_bytes = 0
        # schedule audit trail for the analytical mirror
        # (LLMSimulator.serve(trace=...)): admission order (rids) and
        # (step, rid) preemption events
        self.admission_log: list[int] = []
        self.preemption_log: list[tuple[int, int]] = []
        # scheduling policy (admission / chunk selection / retirement)
        self.scheduler = make_scheduler(cfg, ecfg)
        self.prefilling: dict[int, PrefillState] = {}  # slot -> progress
        # prefix caching runs only where the KV layout can alias blocks
        # (paged, so the backend carries a PrefixIndex), positions map
        # 1:1 to prompt tokens (vlm image prefixes shift every block
        # boundary off the token hashes), and the whole prompt is not
        # needed by a second cache (the speculative draft's contiguous
        # shadow has no block table to alias into)
        self._prefix_on = (
            getattr(self.kv, "prefix", None) is not None
            and not (cfg.family == "vlm" and cfg.n_image_tokens)
            and self.scheduler.name != "speculative")
        # dispatch accounting (the tentpole invariant: 1 per step)
        self.decode_dispatches = 0   # jitted target decode/verify calls
        self.decode_steps = 0        # engine steps that decoded anything
        self.prefills = 0            # whole-prompt (blocking) prefills
        self.prefill_chunk_dispatches = 0
        # speculative accounting (draft dispatches reported separately —
        # the target-model invariant above stays one dispatch per step)
        self.draft_dispatches = 0    # draft prefill + decode dispatches
        self.verify_dispatches = 0   # multi-token target verify calls
        self.spec_row_steps = 0      # (live row, verify step) events
        self.spec_drafted = 0        # candidate tokens actually proposed
        self.spec_committed = 0      # tokens committed by verify steps
        self.spec_draft_accepted = 0  # committed tokens drafted (not bonus)
        # bucketed prefill where right-padding is harmless: causal
        # attention masks pad KV per-row, and recurrent families
        # (ssm/hybrid) run a length-masked scan — pad steps get decay 1
        # and zero input, so the state handed to decode is bitwise the
        # exact-length one. Rolling SWA stays exact-length: its cache
        # would roll the pads in.
        self._bucketed = (ecfg.prefill_bucket_min > 0
                          and cfg.family in MD.TRANSFORMER_FAMILIES
                          + ("audio",) + MD.RECURRENT_FAMILIES
                          and cfg.sliding_window is None)
        # dispatch audit trail: every jitted dispatch appends
        # (step, kind, operand spec tree) — core/costmodel.audit_engine
        # re-traces each entry through the same closures and fails on
        # drift. Specs are ShapeDtypeStructs, so the log stays tiny.
        self.dispatch_log: list[dict] = []
        self.step_index = 0
        # the dispatch graphs: built at module level so the static cost
        # model traces literally the same function objects we jit here.
        # On a mesh, each jit is wrapped to trace under the armed
        # sharding hints (bitwise gather-rows TP); the *closures* stay
        # the untouched module-level functions — the pricer/audit trace
        # them meshless and see the exact same jaxprs as ever.
        self._closures = build_closures(cfg, C)
        self._prefill_one = self._jit(
            self._closures["prefill"])  # one compile per bucket
        # decode/verify donate the cache operand: the new cache aliases
        # the resident one, so a step never holds two caches at once
        self._decode_ragged = self._jit(
            self._closures["decode"], donate=CACHE_ARGNUM)  # one compile
        self._verify_ragged = self._jit(
            self._closures["verify"], donate=CACHE_ARGNUM)  # one compile
        # chunked prefill: slot/hist_len/logit_idx traced -> one compile
        # per chunk shape (two for vlm: first chunk carries the images).
        # The chunk graphs only read the cache (their KV rows land via
        # the backend's donating splice), so they donate nothing.
        self._chunk_fns = {
            "contiguous": self._jit(self._closures["chunk_contiguous"]),
            "paged": self._jit(self._closures["chunk_paged"])}
        self._sample = jax.jit(self._make_sampler())
        # speculative draft: a second, smaller model with its own
        # (always-contiguous) KV cache that shadows the committed
        # sequence. Built only when the policy actually resolved to
        # speculative (unsupported families fall back to blocking and
        # never pay for a draft).
        self.draft_params = self.draft_cfg = self.draft_kv = None
        self.draft_pos = np.zeros(B, np.int32)  # draft-valid KV per slot
        if self.scheduler.name == "speculative":
            self._init_draft(draft_params, draft_cfg)

    def _jit(self, fn, donate=()):
        """``jax.jit`` a dispatch closure (``donate``: argnums whose
        buffers the dispatch consumes); on a mesh, enter the armed
        sharding-hint context around every call. The hints are
        contextvars read at *trace* time, so the first call of each
        shape lowers to the gather-rows tensor-parallel graph and later
        calls hit the compiled cache — still exactly one jitted
        dispatch per step. Outside a mesh this is plain ``jax.jit``."""
        jitted = jax.jit(fn, donate_argnums=donate)
        if self.mesh is None:
            return jitted
        mesh = self.mesh

        def armed(*args, **kwargs):
            with hints.use_mesh(mesh, gather_rows=True):
                return jitted(*args, **kwargs)

        return armed

    def _init_draft(self, draft_params, draft_cfg):
        """Resolve the draft pair: explicit params, a registry arch id
        (smoke-scale, sharing the target's vocab/family), or the
        self-draft fallback reusing the target's first k layers."""
        cfg, ecfg = self.cfg, self.ecfg
        if draft_params is not None:
            dcfg = draft_cfg or cfg
        elif ecfg.draft == "self":
            k = ecfg.spec_draft_layers or max(1, cfg.n_layers // 2)
            draft_params, dcfg = MD.self_draft_params(self.params, cfg, k)
        else:
            from repro.configs import registry
            dcfg = registry.get_smoke_config(ecfg.draft).replace(
                dtype=cfg.dtype)
            draft_params = MD.init_params(
                jax.random.PRNGKey(ecfg.seed), dcfg)
        if dcfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab {dcfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}: speculative acceptance compares "
                "token ids, the models must share a tokenizer")
        if dcfg.family != cfg.family:
            raise ValueError(
                f"draft family {dcfg.family!r} != target family "
                f"{cfg.family!r}: prompt prefixes (e.g. vlm image "
                "tokens) must occupy the same positions in both caches")
        if cfg.family == "vlm" and (
                dcfg.n_image_tokens != cfg.n_image_tokens
                or dcfg.d_model != cfg.d_model):
            raise ValueError(
                f"vlm draft prefix mismatch (n_image_tokens "
                f"{dcfg.n_image_tokens} vs {cfg.n_image_tokens}, "
                f"d_model {dcfg.d_model} vs {cfg.d_model}): the image "
                "prefix must occupy identical positions — and the "
                "shared stub image batch identical feature width — in "
                "both caches")
        if self.mesh is not None:
            draft_params = jax.device_put(
                draft_params,
                SH.param_shardings(
                    self.mesh, jax.eval_shape(lambda: draft_params),
                    serve=True))
        self.draft_params, self.draft_cfg = draft_params, dcfg
        self.draft_kv = ContiguousCache(dcfg, ecfg, mesh=self.mesh)
        # the draft's dispatch graphs are the same module-level
        # closures, built for the draft config (speculative policies
        # only resolve on attention families, so masked is never hit)
        self._draft_closures = build_closures(dcfg, ecfg.max_seq_len)
        self._draft_prefill = self._jit(
            self._draft_closures["prefill"])  # per bucket
        self._draft_decode = self._jit(
            self._draft_closures["decode"],
            donate=CACHE_ARGNUM)  # one compile total

    def _make_sampler(self):
        """Sampling head over returned logits — outside the model jits,
        so backends/layouts can never perturb token selection."""
        mode = self.ecfg.sample
        if mode == "greedy":
            def _sample(logits, seeds, rids, pos):
                return jnp.argmax(logits, -1).astype(jnp.int32)
            return _sample
        if mode == "temperature":
            temp = float(max(self.ecfg.temperature, 1e-6))
            top_k = int(self.ecfg.top_k)

            def _sample(logits, seeds, rids, pos):
                lg = logits.astype(jnp.float32) / temp
                if 0 < top_k < lg.shape[-1]:
                    kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
                    lg = jnp.where(lg < kth, -jnp.inf, lg)

                def row(lgr, s, r, p):
                    key = jax.random.fold_in(
                        jax.random.fold_in(jax.random.PRNGKey(s), r), p)
                    return jax.random.categorical(key, lgr)

                return jax.vmap(row)(lg, seeds, rids, pos).astype(jnp.int32)
            return _sample
        raise ValueError(f"unknown sample mode {mode!r}")

    # -- public API -----------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int | None = None,
               seed: int | None = None, *, tenant: str = "",
               priority: int = 0, slo=None,
               arrival_s: float | None = None) -> Request:
        req = Request(self._next_rid, np.asarray(prompt, np.int32),
                      max_new_tokens, seed=seed, tenant=tenant,
                      priority=int(priority), slo=slo, arrival_s=arrival_s,
                      t_submit=(arrival_s if arrival_s is not None
                                else self._now()))
        self._next_rid += 1
        self.waiting.append(req)
        return req

    def set_now(self, t: float) -> None:
        """Switch to (and advance) the virtual clock — the workload
        replay driver calls this before each step so every latency stamp
        is in deterministic simulated seconds."""
        self.clock = "virtual"
        self.now_s = float(t)

    def _now(self) -> float:
        return self.now_s if self.clock == "virtual" else time.time()

    def has_work(self) -> bool:
        """Anything queued, live, or evicted-but-unfinished."""
        return bool(self.waiting or self.preempted_packets
                    or any(r is not None for r in self.slot_req))

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Drive until all submitted requests finish. Returns finished."""
        steps = 0
        while self.has_work() and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    def _log_dispatch(self, kind: str, *operands):
        """Append one dispatch-audit entry: the kind plus the operand
        spec tree (params excluded — their spec is derivable from
        ``self.params``). ``core/costmodel.audit_engine`` re-traces
        every entry through the matching ``build_closures`` function
        and fails the CI gate on drift."""
        def sds(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
            return x
        self.dispatch_log.append({
            "step": self.step_index, "kind": kind,
            "spec": jax.tree.map(sds, operands)})

    # -- telemetry hooks ---------------------------------------------------
    def _vnow(self):
        """Virtual-clock stamp for spans: the replay clock when driven
        by one, None under the wall clock (spans then carry only their
        perf_counter interval)."""
        return self.now_s if self.clock == "virtual" else None

    def _span(self, name: str, cat: str = "phase", **labels):
        """A telemetry span on this engine's track (no-op when off)."""
        return self.telemetry.span(name, cat=cat, tid=self.tel_label,
                                   now_fn=self._vnow, **labels)

    def _dispatch(self, kind: str, fn, params, *args):
        """Issue one jitted dispatch: always append the audit-log entry;
        with telemetry enabled, additionally time the dispatch to
        completion (``block_until_ready``) under a span named exactly
        like the dispatch kind and feed the profiler a sample keyed to
        the log entry just written — the join ``dispatch_calibration``
        later prices. The result value is identical either way (blocking
        on it early cannot change its bits)."""
        self._log_dispatch(kind, *args)
        tel = self.telemetry
        if not tel.enabled:
            return fn(params, *args)
        idx = len(self.dispatch_log) - 1
        with tel.span(kind, cat="dispatch", tid=self.tel_label,
                      now_fn=self._vnow):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(params, *args))
            dt = time.perf_counter() - t0
        tel.profiler.record(self.tel_label, idx, kind, dt)
        tel.counter("engine_dispatches_total", engine=self.tel_label,
                    kind=kind, kv=self.kv.name).inc()
        tel.histogram("engine_dispatch_wall_s", engine=self.tel_label,
                      kind=kind, kv=self.kv.name).observe(dt)
        return out

    def _finish(self, req: Request):
        """Retire ``req`` into ``finished`` (all five finish sites
        funnel here) and record its latency metrics."""
        self.finished.append(req)
        tel = self.telemetry
        if not tel.enabled:
            return
        tenant = req.tenant or "default"
        prio = str(req.priority)
        tel.counter("engine_requests_total", engine=self.tel_label,
                    tenant=tenant, priority=prio).inc()
        tel.counter("engine_tokens_total", engine=self.tel_label,
                    tenant=tenant, priority=prio).inc(len(req.output))
        tel.histogram("engine_ttft_s", engine=self.tel_label,
                      tenant=tenant, priority=prio).observe(
                          max(0.0, req.ttft_s))
        if len(req.output) > 1:
            tel.histogram("engine_itl_s", engine=self.tel_label,
                          tenant=tenant, priority=prio).observe(
                              max(0.0, req.itl_s))

    def step(self):
        """One engine iteration, orchestrated by the scheduling policy:
        admit -> (at most one prefill-chunk dispatch) -> single ragged
        decode dispatch -> retire. In steady-state decode that is
        exactly one jitted dispatch per step, plus at most one chunk
        dispatch while a prompt is streaming in."""
        self.step_index += 1
        with self._span("step", step=self.step_index):
            with self._span("admit"):
                self.scheduler.admit(self)
            chunk_slot = self.scheduler.select_chunk(self)
            if chunk_slot is not None:
                self._run_chunk(chunk_slot)
            live = np.array([r is not None and i not in self.prefilling
                             for i, r in enumerate(self.slot_req)])
            if live.any():
                if self.draft_kv is not None:
                    self._spec_step(live)
                else:
                    self._decode_step(live)
            with self._span("retire"):
                self.scheduler.retire(self)
        tel = self.telemetry
        if tel.enabled:
            tel.gauge("engine_live_slots", engine=self.tel_label).set(
                sum(r is not None for r in self.slot_req))
            tel.gauge("engine_waiting", engine=self.tel_label).set(
                len(self.waiting))
            tel.gauge("engine_resident_kv_bytes",
                      engine=self.tel_label).set(
                          self.kv.resident_kv_bytes())

    def _decode_step(self, live):
        """The vanilla one-token-per-slot ragged decode dispatch."""
        cache = self.kv.decode_view(self.slot_pos, live)
        args = (jnp.asarray(self.slot_tok), cache,
                jnp.asarray(self.slot_pos), jnp.asarray(live))
        logits, new_cache = self._dispatch(
            "decode", self._decode_ragged, self.params, *args)
        with self._span("kv_commit", cat="kv"):
            self.kv.commit(new_cache)
        self.decode_dispatches += 1
        self.decode_steps += 1
        with self._span("sample"):
            new = np.asarray(self._sample(
                logits, jnp.asarray(self.slot_seed),
                jnp.asarray(self.slot_rid), jnp.asarray(self.slot_pos)))
        for i in np.nonzero(live)[0]:
            req = self.slot_req[i]
            req.output.append(int(new[i]))
            self.slot_tok[i, 0] = int(new[i])
            self.slot_len[i] += 1
            self.slot_pos[i] += 1

    def _spec_step(self, live):
        """One speculative verify step: gamma draft proposals per live
        slot (small-model dispatches), then **one** target dispatch
        verifying every slot's gamma+1 candidate window at its own
        position, then host-side longest-accepted-prefix commit with
        rollback (cache lengths stay at the accepted prefix; paged
        backends free over-allocated blocks).

        Greedy equivalence: candidate i commits iff it equals the
        target's argmax after candidate i-1 — exactly the token vanilla
        greedy decode would have produced — and the first mismatch is
        replaced by that argmax (the bonus token), so the committed
        stream is the vanilla stream regardless of what the draft
        proposed. Budget/EOS/capacity caps are applied to the committed
        prefix in stream order, preserving retirement semantics."""
        B, C = self.ecfg.max_batch, self.ecfg.max_seq_len
        g = self.ecfg.spec_gamma
        # per-row commit cap: budget / capacity bound what the verify
        # could possibly commit, so candidate KV past it never needs a
        # backing block (paged) and candidates past it never need
        # drafting at all
        n_write = np.minimum(
            g + 1, np.maximum(
                1, np.minimum(
                    np.array([self._budget(r) if r is not None else 1
                              for r in self.slot_req]) - self.slot_len,
                    (C - 1) - self.slot_pos)))
        # candidates past the batch-wide commit cap can never commit
        # anywhere — don't draft them, and don't feed padding into the
        # verify either: the window is dispatched at width chain + 1
        # (one compile per distinct width, at most gamma + 1 of them).
        # Padding tokens would be worse than wasted — MoE routing is
        # capacity-based *across* the flattened window, so a column of
        # identical pad tokens concentrates expert load and can evict
        # real tokens from other rows (observed as a greedy divergence
        # on the moe family before this was shape- instead of
        # sentinel-based).
        chain = min(g, int(n_write[live].max()) - 1)
        cand = np.zeros((B, chain), np.int32)
        if chain > 0:
            # -- draft catch-up: a fully-accepted round leaves the
            # draft one committed token behind (the last draft token's
            # KV was never its own input); feed it through before
            # proposing. (chain == 0 rounds retire every live row, so
            # their stale draft state is released by retirement.)
            catch = live & (self.draft_pos < self.slot_pos)
            if catch.any():
                toks = np.zeros((B, 1), np.int32)
                for i in np.nonzero(catch)[0]:
                    req = self.slot_req[i]
                    toks[i, 0] = req.output[
                        int(self.draft_pos[i]) - int(self.slot_nprompt[i])]
                self._draft_dispatch(toks, catch)
                # NOTE: rebind, never `+=` in place — the dispatch
                # above is still in flight (its logits are discarded,
                # so nothing forces it) and on CPU ``jnp.asarray`` may
                # alias the host buffer zero-copy; an in-place bump
                # would race the asynchronous read and corrupt the
                # draft cache nondeterministically.
                self.draft_pos = self.draft_pos + catch
            # -- chained draft proposals over all live slots (ragged)
            cur = self.slot_tok.copy()
            for t in range(chain):
                logits = self._draft_dispatch(cur, live)
                nxt = np.asarray(self._sample(
                    logits, jnp.asarray(self.slot_seed),
                    jnp.asarray(self.slot_rid),
                    jnp.asarray(self.draft_pos)))
                cand[:, t] = nxt
                cur = nxt[:, None].astype(np.int32)
                self.draft_pos = self.draft_pos + live  # rebind (above)
            self.spec_drafted += chain * int(live.sum())
        self._spec_verify_commit(live, cand, n_write, chain)

    def _spec_verify_commit(self, live, cand, n_write, chain):
        """The verify half of a speculative step: one target dispatch
        over every live row's (pending token + ``chain`` candidates)
        window, then host-side longest-accepted-prefix commit and
        rollback. ``chain == 0`` (budget/capacity tail) degenerates to
        a width-1 verify of the pending token alone."""
        # -- one target dispatch verifies the whole ragged batch
        toks = np.concatenate([self.slot_tok, cand], axis=1)  # (B, chain+1)
        cache = self.kv.verify_view(self.slot_pos, live,
                                    np.minimum(n_write, chain + 1))
        args = (jnp.asarray(toks), cache,
                jnp.asarray(self.slot_pos), jnp.asarray(live))
        logits, new_cache = self._dispatch(
            "verify", self._verify_ragged, self.params, *args)
        with self._span("kv_commit", cat="kv"):
            self.kv.commit(new_cache)
        self.decode_dispatches += 1
        self.decode_steps += 1
        self.verify_dispatches += 1
        self.spec_row_steps += int(live.sum())
        with self._span("sample"):
            greedy = np.asarray(self._sample(
                logits, jnp.asarray(self.slot_seed),
                jnp.asarray(self.slot_rid), jnp.asarray(self.slot_pos)))
        # -- host acceptance + commit/rollback
        for i in np.nonzero(live)[0]:
            req = self.slot_req[i]
            a = 0
            while a < chain and cand[i, a] == greedy[i, a]:
                a += 1
            stream = list(cand[i, :a]) + [int(greedy[i, a])]
            committed = []
            for tok in stream[:int(n_write[i])]:
                committed.append(int(tok))
                if tok == self.ecfg.eos_token:
                    break  # vanilla stops after emitting EOS
            n = len(committed)
            req.output.extend(committed)
            req.spec_accepted.append(n)
            self.spec_committed += n
            self.spec_draft_accepted += min(n, a)
            p = int(self.slot_pos[i])
            self.slot_pos[i] = p + n
            self.slot_len[i] += n
            self.slot_tok[i, 0] = committed[-1]
            # target KV valid through the accepted prefix; the draft is
            # valid through the committed tokens it consumed as inputs
            # (it consumed ``chain`` of them this round)
            self.kv.commit_n(i, p + n)
            self.draft_pos[i] = p + min(chain, n)

    def _draft_dispatch(self, toks, live):
        """One ragged draft-model decode dispatch (chain/catch-up)."""
        cache = self.draft_kv.decode_view(self.draft_pos, live)
        args = (jnp.asarray(toks), cache,
                jnp.asarray(self.draft_pos), jnp.asarray(live))
        logits, new_cache = self._dispatch(
            "draft_decode", self._draft_decode, self.draft_params, *args)
        self.draft_kv.commit(new_cache)
        self.draft_dispatches += 1
        return logits

    # -- internals ---------------------------------------------------------
    def _budget(self, req: Request) -> int:
        """Generation budget; an explicit 0 means zero tokens (the old
        ``or``-fallback treated 0 as "use the engine default")."""
        return (req.max_new_tokens if req.max_new_tokens is not None
                else self.ecfg.max_new_tokens)

    def _prompt_cap(self) -> int:
        """Max admissible prompt tokens: KV capacity less one decode slot
        and less any non-token prefix (vlm image tokens share the cache),
        so padded prefill can never overflow into the rolling-cache path."""
        n_prefix = (self.cfg.n_image_tokens
                    if self.cfg.family == "vlm" and self.cfg.n_image_tokens
                    else 0)
        return self.ecfg.max_seq_len - 1 - n_prefix

    def _bucket_len(self, n: int) -> int:
        """Smallest power-of-two bucket >= n (floor ``prefill_bucket_min``),
        capped at the prompt capacity; exact length when bucketing is off."""
        cap = self._prompt_cap()
        if not self._bucketed:
            return min(n, cap)
        b = self.ecfg.prefill_bucket_min
        while b < n:
            b *= 2
        return min(b, cap)

    def _admit_prologue(self, slot: int, req: Request):
        """Shared admission front half: zero-budget insta-finish,
        truncation, cache capacity check. Returns ``(prompt, n_prompt,
        budget)`` when the request should proceed, ``True`` when it was
        consumed without touching the slot, ``False`` to defer it."""
        budget = self._budget(req)
        if budget <= 0:
            # explicit zero-token request: nothing to generate — never
            # runs prefill, never touches the cache
            req.t_first = req.t_done = self._now()
            self._finish(req)
            return True
        cap = self._prompt_cap()
        prompt = req.prompt
        if int(prompt.shape[0]) > cap:
            req.truncated_from = int(prompt.shape[0])
            warnings.warn(
                f"request {req.rid}: prompt truncated from "
                f"{req.truncated_from} to {cap} tokens "
                f"(max_seq_len={self.ecfg.max_seq_len})", stacklevel=5)
            prompt = prompt[:cap]
        n_prompt = int(prompt.shape[0])
        if self.cfg.family == "vlm" and self.cfg.n_image_tokens:
            n_prompt += self.cfg.n_image_tokens
        if not self.kv.can_admit(n_prompt, budget,
                                 prompt=prompt if self._prefix_on
                                 else None):
            return False
        return prompt, n_prompt, budget

    def _admit_one(self, slot: int, req: Request) -> bool:
        """Blocking admission mechanism: run ``req``'s whole prefill in
        one bucketed dispatch and bind it to ``slot``. False when the
        cache backend cannot reserve capacity yet (request stays
        queued). A previously-preempted request resumes from its packet
        instead of re-prefilling (its tokens are already sampled)."""
        if req.rid in self.preempted_packets:
            return self._resume_slot(slot, req)
        pro = self._admit_prologue(slot, req)
        if isinstance(pro, bool):
            return pro
        prompt, n_prompt, budget = pro
        if (self._prefix_on
                and self.kv.prefix_match_tokens(prompt, n_prompt)):
            return self._admit_prefix(slot, req, prompt, n_prompt, budget)
        n = int(prompt.shape[0])
        nb = self._bucket_len(n)
        toks = np.zeros(nb, np.int32)
        toks[:n] = prompt   # right-pad to the bucket length
        batch = {"tokens": jnp.asarray(toks[None, :])}
        if self.cfg.family == "vlm" and self.cfg.n_image_tokens:
            batch["images"] = jnp.zeros(
                (1, self.cfg.n_image_tokens, self.cfg.d_model),
                jnp.bfloat16 if self.cfg.dtype == "bfloat16"
                else jnp.float32)
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.encoder_len, self.cfg.d_model),
                jnp.bfloat16 if self.cfg.dtype == "bfloat16"
                else jnp.float32)
        pre_args = (batch, jnp.asarray(n_prompt - 1, jnp.int32),
                    jnp.asarray(n_prompt, jnp.int32))
        logits, rows = self._dispatch(
            "prefill", self._prefill_one, self.params, *pre_args)
        self.prefills += 1
        self.admission_log.append(req.rid)
        req.prefill_chunks = 1
        seed = req.seed if req.seed is not None else self.ecfg.seed
        tok = self._sample_first(req, seed, logits, n_prompt)
        # admit-time retirement: the prefill token may already hit the
        # budget / EOS / capacity — never occupy a decode slot for it.
        if (budget <= 1 or tok == self.ecfg.eos_token
                or n_prompt >= self.ecfg.max_seq_len - 1):
            req.t_done = self._now()
            self._finish(req)
            return True
        with self._span("kv_splice", cat="kv"):
            self.kv.splice(rows, slot, n_prompt, budget,
                           prompt=prompt if self._prefix_on else None)
        if self._prefix_on:
            # publish the prompt's full blocks as shared (a cold miss:
            # the match above was empty) — the next request with this
            # prefix splices them instead of re-prefilling
            self.kv.register_prefix(slot, prompt, n_prompt)
        if self.draft_kv is not None:
            # speculative: the draft shadows the committed sequence —
            # prefill its cache over the same (bucketed) batch so the
            # chain can propose from position n_prompt immediately
            _, drows = self._dispatch(
                "draft_prefill", self._draft_prefill, self.draft_params,
                *pre_args)
            self.draft_kv.splice(drows, slot, n_prompt, budget)
            self.draft_dispatches += 1
            self.draft_pos[slot] = n_prompt
        self._bind_decode(slot, req, seed, tok, n_prompt)
        return True

    def _admit_prefix(self, slot: int, req: Request, prompt,
                      n_prompt: int, budget: int) -> bool:
        """Warm blocking admission: splice the cached prefix blocks into
        the slot (refcounts bumped, reservation charges only the
        suffix), then prefill just ``prompt[h:]`` with one prefill-over-
        cache chunk dispatch at history offset ``h`` — the PR 3 chunk
        graph, so ``costmodel`` prices it with the same traced closure.
        The suffix is never empty: matches cap at ``(n_prompt - 1) //
        block_size`` blocks, so the prompt's last token always runs to
        produce the admission logits at chunk-local index
        ``n_prompt - 1 - h``. Bitwise equivalence with cold prefill
        follows from determinism of the prompt KV: absolute-position
        RoPE + the same tokens produce the same blocks, so attending
        cached blocks equals re-computing them."""
        h = self.kv.splice_prefix(slot, prompt, n_prompt, budget)
        n_suf = n_prompt - h
        nb = self._bucket_len(n_suf)
        toks = np.zeros(nb, np.int32)
        toks[:n_suf] = prompt[h:]
        batch = {"tokens": jnp.asarray(toks[None, :])}
        view = self.kv.chunk_view(slot)
        fn = self._chunk_fns[view["kind"]]
        sel = (jnp.asarray(view["slot"], jnp.int32)
               if view["kind"] == "contiguous" else view["table"])
        args = (batch, view["k"], view["v"], sel,
                jnp.asarray(h, jnp.int32),
                jnp.asarray(n_suf - 1, jnp.int32))
        logits, ks, vs = self._dispatch(
            f"chunk_{view['kind']}", fn, self.params, *args)
        with self._span("kv_splice", cat="kv"):
            self.kv.splice_partial(ks, vs, slot, h, n_suf)
        self.prefill_chunk_dispatches += 1
        self.admission_log.append(req.rid)
        req.prefill_chunks = 1
        seed = req.seed if req.seed is not None else self.ecfg.seed
        tok = self._sample_first(req, seed, logits, n_prompt)
        if (budget <= 1 or tok == self.ecfg.eos_token
                or n_prompt >= self.ecfg.max_seq_len - 1):
            # admit-time retirement: unlike the cold path, the slot
            # already holds KV (aliased prefix + spliced suffix) —
            # release it (shared refs drop back to the LRU queue)
            req.t_done = self._now()
            self._finish(req)
            self.kv.free(slot)
            return True
        self.kv.register_prefix(slot, prompt, n_prompt)
        self._bind_decode(slot, req, seed, tok, n_prompt)
        return True

    def _start_prefill(self, slot: int, req: Request) -> bool:
        """Chunked admission mechanism: bind ``req`` to ``slot`` and
        reserve its worst-case cache capacity — no dispatch happens
        here; the scheduler streams the prompt in via ``_run_chunk``
        over the following steps. False defers (backend out of
        capacity), True means the request was consumed (bound, or
        insta-finished on a zero budget)."""
        if req.rid in self.preempted_packets:
            return self._resume_slot(slot, req)
        pro = self._admit_prologue(slot, req)
        if isinstance(pro, bool):
            return pro
        prompt, n_prompt, budget = pro
        if self._prefix_on:
            # doubles as the reservation (charging only the uncached
            # suffix); starting the chunk walk at ``done = h`` makes
            # _run_chunk stream in exactly ``prompt[h:]`` at the
            # matched history offset, unchanged
            h = self.kv.splice_prefix(slot, prompt, n_prompt, budget)
        else:
            self.kv.reserve(slot, n_prompt, budget)
            h = 0
        self.admission_log.append(req.rid)
        seed = req.seed if req.seed is not None else self.ecfg.seed
        n_prefix = n_prompt - int(prompt.shape[0])
        self.slot_req[slot] = req
        self.prefilling[slot] = PrefillState(
            prompt=np.asarray(prompt, np.int32), n_prefix=n_prefix,
            n_prompt=n_prompt, budget=budget, seed=seed, done=h)
        return True

    def _run_chunk(self, slot: int):
        """Run the next prefill chunk for ``slot``: one jitted dispatch
        over (chunk tokens) x (cached history), splice the chunk's KV at
        the running offset, and — on the final chunk — sample the first
        token and hand the slot to the decode phase."""
        st = self.prefilling[slot]
        req = self.slot_req[slot]
        ct = self.ecfg.chunk_tokens
        first = st.done == 0
        tok_start = max(0, st.done - st.n_prefix)
        n_tok = min(ct, int(st.prompt.shape[0]) - tok_start)
        toks = np.zeros(ct, np.int32)
        toks[:n_tok] = st.prompt[tok_start:tok_start + n_tok]
        batch = {"tokens": jnp.asarray(toks[None, :])}
        if first and self.cfg.family == "vlm" and self.cfg.n_image_tokens:
            batch["images"] = jnp.zeros(
                (1, self.cfg.n_image_tokens, self.cfg.d_model),
                jnp.bfloat16 if self.cfg.dtype == "bfloat16"
                else jnp.float32)
        n_valid = n_tok + (st.n_prefix if first else 0)
        final = st.done + n_valid >= st.n_prompt
        # logits are read at the prompt's true last position within this
        # chunk — chunk-local index of global position p is p - st.done
        # (only meaningful on the final chunk; 0 otherwise)
        logit_idx = st.n_prompt - 1 - st.done if final else 0
        view = self.kv.chunk_view(slot)
        fn = self._chunk_fns[view["kind"]]
        sel = (jnp.asarray(view["slot"], jnp.int32)
               if view["kind"] == "contiguous" else view["table"])
        args = (batch, view["k"], view["v"], sel,
                jnp.asarray(st.done, jnp.int32),
                jnp.asarray(logit_idx, jnp.int32))
        logits, ks, vs = self._dispatch(
            f"chunk_{view['kind']}", fn, self.params, *args)
        with self._span("kv_splice", cat="kv"):
            self.kv.splice_partial(ks, vs, slot, st.done, n_valid)
        self.prefill_chunk_dispatches += 1
        req.prefill_chunks += 1
        st.done += n_valid
        if not final:
            return
        del self.prefilling[slot]
        tok = self._sample_first(req, st.seed, logits, st.n_prompt)
        if (st.budget <= 1 or tok == self.ecfg.eos_token
                or st.n_prompt >= self.ecfg.max_seq_len - 1):
            req.t_done = self._now()
            self._finish(req)
            self.slot_req[slot] = None
            self.kv.free(slot)
            return
        if self._prefix_on:
            # the prompt's KV is fully resident now — publish its full
            # blocks (hash hits on already-shared blocks are skipped)
            self.kv.register_prefix(slot, st.prompt, st.n_prompt)
        self._bind_decode(slot, req, st.seed, tok, st.n_prompt)

    def _sample_first(self, req: Request, seed: int, logits,
                      n_prompt: int) -> int:
        """Sample the prompt's first token from prefill logits; stamps
        ``t_first`` — TTFT is measured to here, never to an
        intermediate chunk."""
        with self._span("sample"):
            tok = int(np.asarray(self._sample(
                logits, jnp.asarray([seed], jnp.int32),
                jnp.asarray([req.rid], jnp.int32),
                jnp.asarray([n_prompt - 1], jnp.int32)))[0])
        req.t_first = self._now()
        req.output.append(tok)
        return tok

    def _bind_decode(self, slot: int, req: Request, seed: int, tok: int,
                     n_prompt: int):
        """Hand a freshly-prefilled request to the decode phase."""
        self.slot_req[slot] = req
        self.slot_len[slot] = 1
        self.slot_pos[slot] = n_prompt
        self.slot_tok[slot, 0] = tok
        self.slot_rid[slot] = req.rid
        self.slot_seed[slot] = seed
        self.slot_nprompt[slot] = n_prompt

    def _retire_slot(self, i: int):
        """Release slot ``i`` (scheduler-decided retirement)."""
        req = self.slot_req[i]
        req.t_done = self._now()
        self._finish(req)
        self.slot_req[i] = None
        self.slot_len[i] = 0
        self.kv.free(i)
        if self.draft_kv is not None:
            self.draft_kv.free(i)
            self.draft_pos[i] = 0

    # -- preempt-and-requeue (slot <-> host packet) ------------------------
    def _pack_slot(self, slot: int) -> SlotPacket:
        """Snapshot slot ``slot``'s live stream into a host packet and
        release the slot. The cluster wraps this for worker drains; the
        SLO policy wraps it for preemption — same bytes either way."""
        req = self.slot_req[slot]
        n_prompt = int(self.slot_nprompt[slot])
        # prefix provenance rides the packet (the spliced token stream —
        # req.prompt may have been truncated at admission): the importer
        # re-matches it against its own index and aliases whatever it
        # already holds instead of copying the prefix in
        with self._span("kv_export", cat="kv"):
            pkt = SlotPacket(
                req=req, seed=int(self.slot_seed[slot]),
                tok=int(self.slot_tok[slot, 0]),
                pos=int(self.slot_pos[slot]),
                gen_len=int(self.slot_len[slot]),
                n_prompt=n_prompt,
                budget=self._budget(req),
                kv=self.kv.export_slot(
                    slot, int(self.slot_pos[slot]),
                    prompt=(req.prompt[:n_prompt]
                            if self._prefix_on else None),
                    n_prompt=n_prompt if self._prefix_on else None))
        self.slot_req[slot] = None
        self.slot_len[slot] = 0
        self.kv.free(slot)
        return pkt

    def _unpack_slot(self, pkt: SlotPacket, slot: int) -> None:
        """Land a packet in free slot ``slot`` and rebind the stream
        (inverse of :meth:`_pack_slot`; the import re-runs the
        reservation math, so callers must check ``can_admit`` first)."""
        with self._span("kv_import", cat="kv"):
            self.kv.import_slot(pkt.kv, slot, pkt.n_prompt, pkt.budget)
        self.slot_req[slot] = pkt.req
        self.slot_len[slot] = pkt.gen_len
        self.slot_pos[slot] = pkt.pos
        self.slot_tok[slot, 0] = pkt.tok
        self.slot_rid[slot] = pkt.req.rid
        self.slot_seed[slot] = pkt.seed
        self.slot_nprompt[slot] = pkt.n_prompt

    def preempt_slot(self, slot: int) -> SlotPacket:
        """Evict slot ``slot``'s live stream to the waiting queue:
        pack it into a host packet (PR 5's drain path) and requeue the
        request. No token is lost — admission later resumes the stream
        from its exact position, and because sampling is keyed by
        ``(seed, rid, position)`` the resumed greedy stream is bitwise
        identical to an unpreempted run."""
        req = self.slot_req[slot]
        if req is None:
            raise ValueError(f"slot {slot} is not live")
        if slot in self.prefilling:
            raise RuntimeError(
                f"slot {slot} is mid-prefill: chunked prefill state "
                "cannot be packed (no sampled token yet) — preempt only "
                "decode-phase slots")
        if self.draft_kv is not None:
            raise RuntimeError(
                "preemption is unsupported under speculative decoding: "
                "the draft's shadow cache is not part of the export "
                "packet and cannot resume")
        with self._span("preempt", rid=req.rid):
            pkt = self._pack_slot(slot)
        self.preempted_packets[req.rid] = pkt
        req.preemptions += 1
        self.preemptions += 1
        self.preempted_kv_bytes += int(pkt.kv["kv_bytes"])
        self.preemption_log.append((self.step_index, req.rid))
        self.waiting.append(req)
        self.telemetry.counter("engine_preemptions_total",
                               engine=self.tel_label).inc()
        return pkt

    def _resume_slot(self, slot: int, req: Request) -> bool:
        """Admission path for a preempted request: re-import its packet
        into ``slot`` (no prefill — its tokens are already sampled).
        False defers when the cache backend cannot re-admit yet."""
        pkt = self.preempted_packets[req.rid]
        if not self.kv.can_admit(pkt.n_prompt, pkt.budget):
            return False
        del self.preempted_packets[req.rid]
        self._unpack_slot(pkt, slot)
        self.admission_log.append(req.rid)
        return True

    # -- metrics ---------------------------------------------------------------
    def summary(self) -> dict:
        """Serving report. Schema-stable: the key set is identical with
        zero finished requests (zero/NaN-free defaults) and with N —
        callers never guard for missing keys."""
        done = self.finished
        n = len(done)
        lat = [r.latency_s for r in done]
        ttft = [r.ttft_s for r in done]
        itl = [r.itl_s for r in done if len(r.output) > 1]
        toks = sum(len(r.output) for r in done)
        wall = (max(r.t_done for r in done)
                - min(r.t_submit for r in done)) if done else 0.0
        resident = (self.kv.peak_resident_kv_bytes
                    + (self.draft_kv.peak_resident_kv_bytes
                       if self.draft_kv is not None else 0))
        # per-device residency: the KV arrays are partitioned over
        # ``kv_partitions`` devices (heads over ``model``, slot batch
        # over ``data`` for contiguous; 1 without a mesh)
        parts = int(getattr(self.kv, "kv_partitions", 1))
        return {
            "requests": n,
            "tokens": toks,
            # all-zero-duration runs with output keep the historical
            # +inf rates; an empty run reports 0.0, not NaN/inf
            "tokens_per_s": ((toks / wall if wall > 0 else float("inf"))
                             if done else 0.0),
            "qps": ((n / wall if wall > 0 else float("inf"))
                    if done else 0.0),
            "mean_latency_s": float(np.mean(lat)) if lat else 0.0,
            "mean_ttft_s": float(np.mean(ttft)) if ttft else 0.0,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else 0.0,
            "ttft_p99_s": float(np.percentile(ttft, 99)) if ttft else 0.0,
            # ITL only over requests that actually decoded (>=2 tokens);
            # admit-time retirements have no inter-token gap to average
            "mean_itl_s": float(np.mean(itl)) if itl else 0.0,
            "scheduler": self.scheduler.name,
            "prefill_chunks": sum(r.prefill_chunks for r in done),
            "prefill_chunk_dispatches": self.prefill_chunk_dispatches,
            "decode_dispatches": self.decode_dispatches,
            "decode_steps": self.decode_steps,
            "dispatches_per_step": (self.decode_dispatches
                                    / max(1, self.decode_steps)),
            # speculative accounting: verify counts above as the one
            # target dispatch per step; the draft's dispatches (prefill
            # + gamma chain steps + catch-ups) are reported separately
            "draft_dispatches": self.draft_dispatches,
            "verify_dispatches": self.verify_dispatches,
            "spec_gamma": (self.ecfg.spec_gamma
                           if self.draft_kv is not None else 0),
            # per (live slot, verify step): vanilla decode is exactly
            # 1.0 (reported as such for non-speculative engines),
            # perfect acceptance is gamma + 1 — the tokens-per-
            # weight-pass win the CI gate thresholds at > 1.0
            "accepted_tokens_per_step": (
                self.spec_committed / max(1, self.spec_row_steps)
                if self.draft_kv is not None else 1.0),
            # fraction of tokens the draft actually proposed that were
            # committed (skip rounds propose nothing and do not count)
            "acceptance_rate": (
                self.spec_draft_accepted / max(1, self.spec_drafted)
                if self.draft_kv is not None else 0.0),
            "prefills": self.prefills,
            "truncated": sum(r.truncated_from is not None for r in done),
            # SLO-policy preemption accounting (0 under other policies)
            "preemptions": self.preemptions,
            "preempted_kv_bytes": self.preempted_kv_bytes,
            "slo_attainment": (sum(r.slo_met for r in done) / n
                               if n else 1.0),
            **request_breakdowns(done),
            "kv_cache": self.kv.name,
            # prefix-cache accounting (zeros where the backend has no
            # index): token hit rate over admitted prompts, admissions
            # with a nonzero match, shared-pool residency and LRU churn
            "prefix_hit_rate": float(
                getattr(self.kv, "prefix_hit_rate", 0.0)),
            "prefix_hits": int(getattr(self.kv, "prefix_hits", 0)),
            "prefix_hit_tokens": int(
                getattr(self.kv, "prefix_hit_tokens", 0)),
            "prefix_lookups": int(getattr(self.kv, "prefix_lookups", 0)),
            "prefix_evictions": (
                self.kv.prefix.evictions
                if getattr(self.kv, "prefix", None) is not None else 0),
            "resident_shared_kv_bytes": int(
                getattr(self.kv, "resident_shared_kv_bytes", 0)),
            # peak bytes the cache backend actually held vs. what a
            # dense max_batch x max_seq_len cache charges regardless;
            # a speculative engine also holds the draft's contiguous
            # shadow cache — report it, and charge it to the total
            "draft_kv_bytes": (self.draft_kv.peak_resident_kv_bytes
                               if self.draft_kv is not None else 0),
            "resident_kv_bytes": resident,
            "contiguous_kv_bytes": contiguous_kv_bytes(
                self.cfg, self.ecfg.max_batch, self.ecfg.max_seq_len),
            # mesh-serving accounting: the (data, model) shape (None on
            # a single device), devices spanned, and the residency each
            # device actually holds of the sharded KV pool
            "mesh": self.ecfg.mesh,
            "mesh_devices": (self.mesh.devices.size
                             if self.mesh is not None else 1),
            "kv_partitions": parts,
            "resident_kv_bytes_per_device": -(-resident // parts),
            # telemetry fold-in: always present; all-zero when disabled
            "telemetry": self.telemetry.engine_aggregates(self.tel_label),
        }
