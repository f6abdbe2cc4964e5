"""Pluggable KV-cache managers for the serving engine.

The paper's cloud-scenario efficiency (§1.2, §3.4) hinges on how much
KV state stays resident per admitted request: a contiguous layout
charges every decode slot the full ``max_seq_len`` capacity even when
its request only ever touches a fraction of it. This module makes the
cache layout an explicit seam — :class:`KVCacheManager` is the protocol
the engine (and the analytical simulator) consume, with two backends:

- :class:`ContiguousCache` — the classic dense ``(L, B, C, H, Dh)``
  layout; per-slot rows spliced/overwritten in place. Capacity cost is
  ``max_batch * max_seq_len`` positions regardless of workload. The
  only layout recurrent families (ssm/hybrid) and rolling SWA caches
  support.
- :class:`PagedCache` — vLLM-style block-table layout for attention
  families: one shared pool of fixed-size KV blocks ``(L, NB, bs, H,
  Dh)`` plus a host-side per-slot block table and free-list allocator.
  Blocks are allocated lazily (prefill allocates just the prompt's
  blocks, decode allocates one block per ``bs`` generated tokens) and
  freed at retirement, so resident KV bytes track what requests
  actually use — and admission can oversubscribe positions relative to
  a contiguous cache of the same byte budget.

Admission safety: ``PagedCache`` reserves (but does not allocate) the
worst-case block count of every admitted request — ``can_admit`` only
accepts a request when the free pool covers all outstanding
reservations, so an admitted request can never deadlock mid-decode.

Prefix caching (opt-in via ``EngineConfig.prefix_cache``): full
``block_size``-token blocks of the prompt stream are content-hashed
(chained, so a block's identity covers everything before it) into a
per-cache :class:`PrefixIndex` of immutable shared blocks with
refcounts. Admission matches the longest cached block-aligned prefix
and splices those block IDs into the new slot's table instead of
re-prefilling them; the reservation charges only the uncached suffix.
Copy-on-write holds structurally: only blocks wholly inside the prompt
are ever shared, and every post-prefill write lands at position
``>= n_prompt`` — i.e. in a privately allocated block — so a shared
block is never written in place. Refcount-zero shared blocks stay
resident (that is the cache) and are evicted LRU-first under pool
pressure; the availability invariant ``free + evictable >= sum of
reservations`` keeps admission deadlock-free with phantom (evictable)
credit counted.

The decode-view contract: ``decode_view(pos, live)`` returns the device
pytree ``decode_step`` consumes. Contiguous returns the dense cache;
paged returns ``{"k": pool, "v": pool, "block_tab": (B, W) int32,
"len": ...}`` and ``model.decode_step`` follows the block-table
indirection (gathered per-layer views for attention, per-row
block/offset scatter for the new token's KV).
"""
from __future__ import annotations

import hashlib
import math
import warnings
from functools import partial
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as SH
from repro.models import model as MD


# ---------------------------------------------------------------------------
# shared byte accounting (engine summary + analytical simulator)
# ---------------------------------------------------------------------------

def kv_partition_count(arr) -> int:
    """Devices holding *distinct* shards of ``arr`` (1 when replicated
    or unsharded) — the divisor that turns the backend's logical
    resident-KV accounting into per-device bytes."""
    try:
        shard = arr.sharding.shard_shape(tuple(arr.shape))
    except AttributeError:
        return 1
    total = int(np.prod(arr.shape)) or 1
    per = int(np.prod(shard)) or 1
    return max(1, total // per)

def kv_bytes_per_token(cfg) -> int:
    """Bytes of self-attention KV state one cached position occupies
    across all layers (0 for pure-recurrent families)."""
    st = MD.cache_struct(cfg, 1, 1)
    total = 0
    for name in ("k", "v"):
        if name in st:
            sh, dt = st[name]
            total += int(np.prod(sh)) * np.dtype(dt).itemsize
    return total


def contiguous_kv_bytes(cfg, batch: int, capacity: int) -> int:
    """Total cache footprint of the dense layout (every leaf but the
    position counter) — the ``max_batch x max_seq_len`` charge."""
    total = 0
    for name, (sh, dt) in MD.cache_struct(cfg, batch, capacity).items():
        if name == "len":
            continue
        total += int(np.prod(sh)) * np.dtype(dt).itemsize
    return total


def paged_resident_kv_bytes(cfg, lens, block_size: int) -> int:
    """Resident bytes of a paged cache holding ``lens[i]`` positions per
    request: allocated blocks only, each rounded up to ``block_size``."""
    blocks = sum(math.ceil(n / block_size) for n in lens)
    return blocks * block_size * kv_bytes_per_token(cfg)


# ---------------------------------------------------------------------------
# protocol
# ---------------------------------------------------------------------------

@runtime_checkable
class KVCacheManager(Protocol):
    """What the serving engine needs from a cache backend."""

    name: str

    def can_admit(self, n_prompt: int, budget: int,
                  prompt=None) -> bool:
        """True if capacity exists for a request of this prompt length
        and generation budget (worst case, no mid-decode failure).
        ``prompt`` (the token stream) lets prefix-caching backends
        charge only the uncached suffix; backends without a prefix
        index ignore it."""
        ...

    def splice(self, rows: dict, slot: int, n_prompt: int,
               budget: int, prompt=None) -> None:
        """Write a batch-1 prefill cache into ``slot``. ``prompt`` is
        cold-miss accounting context for prefix-caching backends."""
        ...

    def reserve(self, slot: int, n_prompt: int, budget: int) -> None:
        """Register the worst-case capacity of a request admitted for
        *chunked* prefill into ``slot`` before any KV lands. Paged
        backends hold the reservation so later admissions cannot eat
        the blocks this request still needs (deadlock freedom); each
        :meth:`splice_partial` / :meth:`decode_view` allocation then
        pays the reservation down. No-op for contiguous."""
        ...

    def splice_partial(self, k_rows, v_rows, slot: int, offset: int,
                       n_valid: int) -> None:
        """Write one prefill chunk's KV rows (L, 1, S, H, Dh) into
        ``slot`` at positions ``offset .. offset + n_valid - 1`` —
        callable repeatedly at a running offset; rows past ``n_valid``
        (the right-pad of a short final chunk) are dropped. Paged
        backends allocate exactly the blocks the span touches."""
        ...

    def chunk_view(self, slot: int) -> dict:
        """Device operands for one chunked-prefill dispatch over this
        slot's cached history: ``{"kind": "contiguous", "k", "v",
        "slot"}`` (dense per-layer rows, slot selected inside the jit)
        or ``{"kind": "paged", "k", "v", "table"}`` (block pools plus
        the slot's table row, gathered inside the jit). Valid length is
        tracked by the caller and masks everything else."""
        ...

    def decode_view(self, pos: np.ndarray, live: np.ndarray) -> dict:
        """Device cache pytree for one ragged decode dispatch (allocates
        any block the step is about to write, for paged backends)."""
        ...

    def verify_view(self, pos: np.ndarray, live: np.ndarray,
                    n_tokens: np.ndarray) -> dict:
        """Device cache pytree for one speculative **verify** dispatch:
        like :meth:`decode_view`, but the step is about to write up to
        ``n_tokens[i]`` candidate KVs at ``pos[i] .. pos[i] +
        n_tokens[i] - 1`` per live row. Paged backends allocate every
        block that window touches (paying the reservation down —
        ``n_tokens`` is the row's *commit cap*, which the admission
        reservation already covers); writes beyond it land on sentinel
        table entries and are dropped in the dispatch."""
        ...

    def commit_n(self, slot: int, n_valid: int) -> None:
        """Speculative rollback/commit: after host-side acceptance, the
        slot's cache is valid only to position ``n_valid - 1`` —
        everything the verify dispatch wrote past it is rejected-
        candidate garbage. Contiguous backends need no action (the
        per-row length vector masks it and the next dispatch
        overwrites); paged backends free every allocated block wholly
        past the new valid span and re-credit the reservation, so
        resident bytes return to what the accepted prefix needs."""
        ...

    def commit(self, new_cache: dict) -> None:
        """Store the cache pytree returned by the decode dispatch."""
        ...

    def free(self, slot: int) -> None:
        """Release slot state at retirement."""
        ...

    def export_slot(self, slot: int, n_valid: int, prompt=None,
                    n_prompt=None) -> dict:
        """Pack slot ``slot``'s live cache state — KV positions
        ``0 .. n_valid - 1`` plus any recurrent/cross state — into a
        host-side packet for handoff to another worker's cache
        (disaggregated prefill→decode, or mid-stream slot migration).
        The packet is backend-portable: KV travels as dense per-layer
        rows, so a paged exporter can hand off to a contiguous importer
        and vice versa. ``packet["kv_bytes"]`` is the number of bytes
        that crossed the device boundary (what the cluster charges as
        transfer cost). ``prompt``/``n_prompt``, when given, attach
        prefix provenance so a prefix-caching importer can re-match the
        prompt against its own index and alias instead of copying."""
        ...

    def import_slot(self, packet: dict, slot: int, n_prompt: int,
                    budget: int) -> None:
        """Unpack a :meth:`export_slot` packet into ``slot`` on this
        (importing) cache. ``n_prompt``/``budget`` are the request's
        original admission parameters: paged backends re-run the
        worst-case reservation math against them — allocate the blocks
        the packet's positions need now, hold the rest as a reservation
        — so a migrated request can no more deadlock the pool than a
        locally admitted one. Callers must gate on :meth:`can_admit`
        with the same arguments first."""
        ...

    def resident_kv_bytes(self) -> int:
        """Bytes of KV state currently resident."""
        ...

    @property
    def peak_resident_kv_bytes(self) -> int:
        """High-water mark of :meth:`resident_kv_bytes` over the run
        (what ``ServingEngine.summary`` reports)."""
        ...


# ---------------------------------------------------------------------------
# block allocator (host-side free list)
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Free-list allocator over ``num_blocks`` fixed-size KV blocks.

    Guards the two classic allocator bugs: double-free (freeing a block
    that is not allocated raises) and leakage (accounting is exact:
    ``free_blocks + allocated_blocks == num_blocks`` always).
    """

    def __init__(self, num_blocks: int):
        if num_blocks <= 0:
            raise ValueError(f"need at least one block, got {num_blocks}")
        self.num_blocks = num_blocks
        # pop from the end -> block 0 handed out first (deterministic)
        self._free = list(range(num_blocks - 1, -1, -1))
        self._allocated: set[int] = set()
        self.peak_allocated = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def allocated_blocks(self) -> int:
        return len(self._allocated)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("KV block pool exhausted (reservation "
                               "accounting should have prevented this)")
        blk = self._free.pop()
        self._allocated.add(blk)
        self.peak_allocated = max(self.peak_allocated, len(self._allocated))
        return blk

    def free(self, blk: int) -> None:
        if blk not in self._allocated:
            raise ValueError(f"double free or foreign block: {blk}")
        self._allocated.remove(blk)
        self._free.append(blk)


# ---------------------------------------------------------------------------
# prefix index (hash-chained shared blocks with refcounts + LRU)
# ---------------------------------------------------------------------------

def _chain_hash(prev: bytes, tokens: np.ndarray) -> bytes:
    """One link of the block hash chain: the digest covers the previous
    link, so equal hashes imply equal *prefixes*, not just equal blocks."""
    data = prev + np.ascontiguousarray(tokens, np.int64).tobytes()
    return hashlib.blake2b(data, digest_size=16).digest()


class PrefixIndex:
    """Content-hash registry of immutable shared KV blocks.

    Each entry maps the chained hash of one full ``block_size``-token
    prompt block (hash covers all tokens up to and including the block)
    to a pool block id plus a refcount — the number of slot tables
    currently aliasing the block. Refcount-zero entries stay resident
    and form an LRU queue; :meth:`evict_lru` unregisters the coldest
    one when the pool needs its block back.

    The same class backs both the engine's :class:`PagedCache` and the
    analytical mirror's ledger (virtual block ids), so the hit/miss/
    eviction schedule is reproduced by construction, not by a re-
    implementation.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self._by_hash: dict[bytes, int] = {}
        self._hash_of: dict[int, bytes] = {}
        self._refs: dict[int, int] = {}
        self._lru: dict[int, None] = {}   # insertion-ordered: oldest first
        self.evictions = 0

    # -- queries ----------------------------------------------------------
    def keys_for(self, prompt, n_blocks: int) -> list[bytes]:
        """Chained hash keys of the first ``n_blocks`` full blocks."""
        bs = self.block_size
        arr = np.asarray(prompt, np.int64)[:n_blocks * bs]
        keys, key = [], b""
        for k in range(n_blocks):
            key = _chain_hash(key, arr[k * bs:(k + 1) * bs])
            keys.append(key)
        return keys

    def match(self, prompt, n_prompt: int) -> list[int]:
        """Block ids of the longest cached block-aligned prefix. Capped
        at ``(n_prompt - 1) // block_size`` blocks: at least one suffix
        token must still run through prefill to produce the admission
        logits."""
        bs = self.block_size
        limit = max(0, (int(n_prompt) - 1) // bs)
        ids: list[int] = []
        if not limit:
            return ids
        arr = np.asarray(prompt, np.int64)[:limit * bs]
        key = b""
        for k in range(limit):
            key = _chain_hash(key, arr[k * bs:(k + 1) * bs])
            bid = self._by_hash.get(key)
            if bid is None:
                break
            ids.append(bid)
        return ids

    def holds(self, bid: int) -> bool:
        return bid in self._hash_of

    def refcount(self, bid: int) -> int:
        return self._refs.get(bid, 0)

    @property
    def resident_blocks(self) -> int:
        return len(self._hash_of)

    def evictable(self, excluding=()) -> int:
        """Refcount-zero resident blocks the pool could reclaim, minus
        any the caller is about to acquire."""
        if not excluding:
            return len(self._lru)
        return len(self._lru) - len(set(excluding) & self._lru.keys())

    # -- mutation ---------------------------------------------------------
    def acquire(self, ids) -> None:
        """Alias shared blocks into one more slot table (revives any
        refcount-zero entry out of the LRU queue)."""
        for bid in ids:
            self._refs[bid] = self._refs.get(bid, 0) + 1
            self._lru.pop(bid, None)

    def release(self, bid: int) -> None:
        """Drop one table's alias; at refcount zero the block joins the
        LRU queue (still resident — that is the cache)."""
        n = self._refs.get(bid, 0) - 1
        if n < 0:
            raise RuntimeError(f"refcount underflow on shared block {bid}")
        self._refs[bid] = n
        if n == 0:
            self._lru[bid] = None

    def register(self, key: bytes, bid: int) -> bool:
        """Publish ``bid`` as the canonical block for ``key`` with one
        reference (the registering slot's own table). Returns False if
        the key already has a canonical block — the caller keeps its
        private copy."""
        if key in self._by_hash:
            return False
        self._by_hash[key] = bid
        self._hash_of[bid] = key
        self._refs[bid] = 1
        return True

    def evict_lru(self):
        """Unregister and return the coldest refcount-zero block id (the
        caller returns it to the allocator), or None."""
        if not self._lru:
            return None
        bid = next(iter(self._lru))
        del self._lru[bid]
        key = self._hash_of.pop(bid)
        del self._by_hash[key]
        del self._refs[bid]
        self.evictions += 1
        return bid


EXPORT_QUANTUM = 16   # exported KV spans round up to this many positions
                      # (bounded set of handoff shapes -> bounded compiles)


def _export_span(n_valid: int) -> int:
    """Positions an exported KV row carries for ``n_valid`` valid ones."""
    n = max(int(n_valid), 1)
    return math.ceil(n / EXPORT_QUANTUM) * EXPORT_QUANTUM


# ---------------------------------------------------------------------------
# cache jits: each update donates the cache it rewrites, so it aliases
# the resident buffer in place instead of holding two copies
# ---------------------------------------------------------------------------

@partial(jax.jit, donate_argnums=0)
def splice_rows(cache: dict, rows: dict, slot):
    """Land a batch-1 cache (every batched leaf: KV rows, recurrent or
    cross state) in row ``slot`` of the dense cache. ``slot`` is traced:
    one compile total."""
    axes = MD.cache_batch_axes(cache)
    return {name: (leaf if axes[name] is None
                   else jax.lax.dynamic_update_slice_in_dim(
                       leaf, rows[name].astype(leaf.dtype), slot,
                       axes[name]))
            for name, leaf in cache.items()}


@partial(jax.jit, donate_argnums=(0, 1))
def splice_chunk(cache_k, cache_v, rows_k, rows_v, slot, offset, n_valid):
    """One prefill chunk's rows (L, 1, S, H, Dh) -> positions ``offset ..
    offset + n_valid - 1`` of row ``slot``; the pad tail (and anything
    past the capacity) is dropped, never clamped back onto real
    positions. One compile per chunk shape."""
    return (MD.write_window(cache_k, rows_k, slot, offset, n_valid),
            MD.write_window(cache_v, rows_v, slot, offset, n_valid))


@partial(jax.jit, donate_argnums=(0, 1))
def splice_blocks(pool_k, pool_v, rows_k, rows_v, blocks):
    """Dense rows (L, 1, n*bs, H, Dh) -> pool blocks ``blocks`` (n,):
    block i of the rows lands in ``blocks[i]``; sentinel entries (>= NB,
    the pad blocks past a prompt) are dropped. Used at admission (n = W,
    one compile) and at handoff import (one compile per block count)."""
    return (MD.write_pool_blocks(pool_k, rows_k, blocks),
            MD.write_pool_blocks(pool_v, rows_v, blocks))


@jax.jit
def gather_blocks(pool, blocks):
    """Pool blocks ``blocks`` (n,) of every layer as dense rows
    (L, 1, n*bs, H, Dh). Gathers layer by layer: one gather along the
    block axis of all layers at once makes the TPU compiler relayout
    the whole pool into a temporary. One compile per block count."""
    rows = jax.lax.map(lambda layer: layer[blocks], pool)
    return rows.reshape(pool.shape[0], 1, -1, *pool.shape[3:])


@partial(jax.jit, donate_argnums=(0, 1))
def splice_positions(pool_k, pool_v, rows_k, rows_v, blk, off):
    """Per-position scatter for chunked prefill: position i of the
    chunk (L, 1, S, H, Dh) lands in pool block ``blk[i]`` at row
    ``off[i]`` (sentinel entries — the pad tail — are dropped). No
    alignment requirement between chunk offsets and the block size: a
    vlm image prefix can shift every chunk boundary. One compile per
    chunk shape."""
    return (MD.write_pool_positions(pool_k, rows_k, blk[None], off[None]),
            MD.write_pool_positions(pool_v, rows_v, blk[None], off[None]))


def _init_sharded(make, shardings_of):
    """``make()``'s arrays created in place under ``shardings_of(shape)``:
    each device allocates only its own shard. Building the whole cache
    on one device and then ``device_put``-ing it would hold a full
    unsharded copy there (beside that device's weights) at start-up."""
    return jax.jit(make, out_shardings=shardings_of(jax.eval_shape(make)))()


# ---------------------------------------------------------------------------
# contiguous backend (the original layout, behind the protocol)
# ---------------------------------------------------------------------------

class ContiguousCache:
    """Dense per-slot cache: every slot owns ``max_seq_len`` positions
    (plus any recurrent state), spliced/overwritten in place."""

    name = "contiguous"

    def __init__(self, cfg, ecfg, mesh=None):
        self.cfg = cfg
        B, C = ecfg.max_batch, ecfg.max_seq_len
        self.kv_partitions = 1
        if mesh is None:
            self._cache = MD.init_cache(cfg, B, C)
        else:
            # serve-mode mesh: batch over ``data``, heads over ``model``
            # (sequence-sharded fallback when heads don't divide) — the
            # same rule the dry-run lowers under, so the resident pool
            # lives sharded next to the attention heads that read it.
            self._cache = _init_sharded(
                lambda: MD.init_cache(cfg, B, C),
                lambda shape: SH.cache_shardings(mesh, shape, cfg))
            if "k" in self._cache:
                self.kv_partitions = kv_partition_count(self._cache["k"])
        self._footprint = contiguous_kv_bytes(cfg, B, C)
        # occupancy, for the double-import guard: the dense layout has
        # no allocator to notice a clobber, so track which slots hold a
        # live (spliced or imported, not yet freed) stream explicitly
        self._occupied: set[int] = set()

    def can_admit(self, n_prompt: int, budget: int, prompt=None) -> bool:
        return True  # every slot already owns full capacity

    def splice(self, rows: dict, slot: int, n_prompt: int,
               budget: int, prompt=None) -> None:
        self._occupied.add(slot)
        self._cache = splice_rows(self._cache, rows,
                                  jnp.asarray(slot, jnp.int32))

    def reserve(self, slot: int, n_prompt: int, budget: int) -> None:
        self._occupied.add(slot)  # capacity is pre-provisioned per slot

    def splice_partial(self, k_rows, v_rows, slot: int, offset: int,
                       n_valid: int) -> None:
        self._cache["k"], self._cache["v"] = splice_chunk(
            self._cache["k"], self._cache["v"], k_rows, v_rows,
            jnp.asarray(slot, jnp.int32), jnp.asarray(offset, jnp.int32),
            jnp.asarray(n_valid, jnp.int32))

    def chunk_view(self, slot: int) -> dict:
        return {"kind": "contiguous", "k": self._cache["k"],
                "v": self._cache["v"], "slot": slot}

    def decode_view(self, pos, live) -> dict:
        return self._cache

    def verify_view(self, pos, live, n_tokens) -> dict:
        return self._cache  # every slot already owns full capacity

    def commit_n(self, slot: int, n_valid: int) -> None:
        pass  # rejected-candidate KV is masked by the per-row length
        # vector and overwritten in place by the next dispatch

    def commit(self, new_cache: dict) -> None:
        self._cache = new_cache

    def free(self, slot: int) -> None:
        self._occupied.discard(slot)  # rows are overwritten by the
        # next admit; only the occupancy mark needs releasing

    def export_slot(self, slot: int, n_valid: int, prompt=None,
                    n_prompt=None) -> dict:
        """Pack the slot's row of every batched leaf. KV leaves are
        position-sliced to ``n_valid`` rounded up to the export quantum
        (bounded set of import-splice shapes); recurrent / cross-
        attention leaves travel whole — they are O(1) in the sequence
        length. ``prompt``/``n_prompt`` (prefix provenance) are accepted
        for signature parity and ignored: the dense layout shares
        nothing."""
        axes = MD.cache_batch_axes(self._cache)
        packet = {"n_valid": int(n_valid)}
        nbytes = 0
        for name, arr in self._cache.items():
            ax = axes[name]
            if ax is None:
                continue
            row = jax.lax.dynamic_slice_in_dim(arr, slot, 1, axis=ax)
            if name in ("k", "v"):
                p = min(_export_span(n_valid), arr.shape[2])
                row = jax.lax.slice_in_dim(row, 0, p, axis=2)
            host = np.asarray(jax.device_get(row))
            packet[name] = host
            nbytes += host.nbytes
        packet["kv_bytes"] = nbytes
        return packet

    def import_slot(self, packet: dict, slot: int, n_prompt: int,
                    budget: int) -> None:
        if slot in self._occupied:
            raise RuntimeError(
                f"import_slot into occupied slot {slot}: a live stream's "
                "KV would be silently clobbered — free the slot first "
                "(preemption/requeue must never double-import)")
        self._occupied.add(slot)
        axes = MD.cache_batch_axes(self._cache)
        rows = {}
        for name, arr in self._cache.items():
            ax = axes[name]
            if ax is None:
                continue
            row = packet[name]
            if name in ("k", "v") and row.shape[2] != arr.shape[2]:
                # zero-pad the exported span back to full capacity so
                # the admission splice (one compiled shape) can land it;
                # pad positions are garbage the per-row length masks,
                # and decode overwrites them as the stream advances.
                pad = [(0, 0)] * row.ndim
                pad[2] = (0, arr.shape[2] - row.shape[2])
                row = np.pad(row, pad)
            rows[name] = jnp.asarray(row)
        self._cache = splice_rows(self._cache, rows,
                                  jnp.asarray(slot, jnp.int32))

    def resident_kv_bytes(self) -> int:
        return self._footprint

    @property
    def peak_resident_kv_bytes(self) -> int:
        return self._footprint


# ---------------------------------------------------------------------------
# paged backend (block tables over a shared pool)
# ---------------------------------------------------------------------------

class PagedCache:
    """Block-table cache for attention families (dense/moe/vlm, no
    sliding window): a shared ``(L, NB, bs, H, Dh)`` pool, a host-side
    per-slot block table, lazy allocation, retirement-time free."""

    name = "paged"

    def __init__(self, cfg, ecfg, mesh=None):
        if cfg.family not in MD.TRANSFORMER_FAMILIES:
            raise ValueError(f"paged cache does not support family "
                             f"{cfg.family!r}")
        if cfg.sliding_window is not None:
            raise ValueError("paged cache does not support rolling SWA "
                             "caches (already capacity-bounded)")
        bs, C = ecfg.kv_block_size, ecfg.max_seq_len
        if bs <= 0 or C % bs:
            raise ValueError(
                f"kv_block_size={bs} must be positive and divide "
                f"max_seq_len={C} (the gathered decode view must match "
                "the contiguous capacity bitwise)")
        self.cfg = cfg
        self.block_size = bs
        self.table_width = W = C // bs
        self.num_blocks = NB = ecfg.kv_blocks or ecfg.max_batch * W
        self._bytes_per_token = kv_bytes_per_token(cfg)
        self.kv_partitions = 1
        if mesh is None:
            self._pool_k, self._pool_v = MD.init_paged_pools(cfg, NB, bs)
        else:
            # heads over ``model``; block/position dims stay whole (a
            # position split would break the bitwise decode contract)
            self._pool_k, self._pool_v = _init_sharded(
                lambda: MD.init_paged_pools(cfg, NB, bs),
                lambda shape: SH.pool_shardings(mesh, shape))
            self.kv_partitions = kv_partition_count(self._pool_k)
        B = ecfg.max_batch
        # NB is the sentinel "no block" id: jitted scatters drop it,
        # gathers clamp it onto a real (masked-off) block.
        self.table = np.full((B, W), NB, np.int32)
        self.allocator = BlockAllocator(NB)
        self._reserved = np.zeros(B, np.int64)
        self._max_seq_len = C
        # opt-in prefix caching: hash-chained shared blocks + refcounts
        self.prefix = (PrefixIndex(bs)
                       if getattr(ecfg, "prefix_cache", False) else None)
        self._shared: list[set[int]] = [set() for _ in range(B)]
        self.prefix_lookups = 0        # admissions that consulted the index
        self.prefix_hits = 0           # admissions with a nonzero match
        self.prefix_hit_tokens = 0     # prompt tokens served from cache
        self.prefix_lookup_tokens = 0  # prompt tokens across lookups

    # -- accounting -------------------------------------------------------
    def _need_blocks(self, n_prompt: int, budget: int) -> int:
        """Worst-case blocks a request ever touches: positions
        ``0 .. n_prompt + budget - 2`` (the last generated token's KV is
        never written), capped by the retirement bound ``C - 1``."""
        n_pos = min(n_prompt + max(budget, 1) - 1, self._max_seq_len - 1)
        return math.ceil(max(n_pos, 1) / self.block_size)

    def can_admit(self, n_prompt: int, budget: int, prompt=None) -> bool:
        need = self._need_blocks(n_prompt, budget)
        if need > self.allocator.num_blocks:
            raise ValueError(
                f"request needs {need} KV blocks but the pool only has "
                f"{self.allocator.num_blocks}; raise kv_blocks or lower "
                "max_new_tokens")
        outstanding = int(self._reserved.sum())
        avail = self.allocator.free_blocks - outstanding
        if self.prefix is not None:
            # a cached prefix charges nothing; refcount-zero resident
            # blocks (minus the ones this match is about to revive) are
            # evictable on demand, so they count as available — the
            # ``free + evictable >= sum(reserved)`` invariant keeps the
            # phantom credit deadlock-free. The evictable credit applies
            # even without a prompt (the conservative resume/route gate):
            # otherwise a pool parked entirely in the zero-ref LRU would
            # refuse a resume forever with nothing left to free it.
            ids = (self.prefix.match(prompt, n_prompt)
                   if prompt is not None else [])
            need -= len(ids)
            avail += self.prefix.evictable(excluding=ids)
        return avail >= need

    def prefix_match_tokens(self, prompt, n_prompt: int) -> int:
        """Tokens of the longest cached block-aligned prefix (a pure
        query — no counters, no refcounts; the router uses this too)."""
        if self.prefix is None:
            return 0
        return len(self.prefix.match(prompt, n_prompt)) * self.block_size

    def _alloc_block(self) -> int:
        """Allocate one pool block, evicting LRU refcount-zero shared
        blocks under pressure (the freed id is handed right back out)."""
        if self.prefix is not None and self.allocator.free_blocks == 0:
            bid = self.prefix.evict_lru()
            if bid is not None:
                self.allocator.free(bid)
        return self.allocator.alloc()

    def _free_block(self, blk: int) -> None:
        """Return a privately-held block to the allocator. Freeing a
        block the prefix index still refcounts would alias-corrupt the
        pool (another slot's table points at it) — raise instead."""
        if self.prefix is not None:
            if self.prefix.refcount(blk) > 0:
                raise RuntimeError(
                    f"freeing shared block {blk} with refcount "
                    f"{self.prefix.refcount(blk)}: another slot's table "
                    "still aliases it — release via the prefix index, "
                    "never the raw allocator")
            if self.prefix.holds(blk):
                raise RuntimeError(
                    f"freeing registered shared block {blk} outside the "
                    "eviction path: the index would map its hash to a "
                    "recycled id")
        self.allocator.free(blk)

    # -- protocol ---------------------------------------------------------
    def splice(self, rows: dict, slot: int, n_prompt: int,
               budget: int, prompt=None) -> None:
        now = math.ceil(n_prompt / self.block_size)
        blocks = [self._alloc_block() for _ in range(now)]
        self.table[slot, :now] = blocks
        self._reserved[slot] = self._need_blocks(n_prompt, budget) - now
        vec = np.full(self.table_width, self.num_blocks, np.int32)
        vec[:now] = blocks
        self._pool_k, self._pool_v = splice_blocks(
            self._pool_k, self._pool_v, rows["k"], rows["v"],
            jnp.asarray(vec))
        if self.prefix is not None and prompt is not None:
            # a cold full prefill under prefix mode: count the miss
            self.prefix_lookups += 1
            self.prefix_lookup_tokens += int(n_prompt)

    def splice_prefix(self, slot: int, prompt, n_prompt: int,
                      budget: int) -> int:
        """Install the longest cached block-aligned prefix into the
        slot's table (aliasing shared blocks, refcounts bumped) and set
        the reservation to charge only the uncached suffix. Returns the
        matched prefix length in tokens — the caller prefills only
        ``prompt[h:]`` at history offset ``h``. With no match this
        degenerates to :meth:`reserve` plus miss accounting."""
        assert self.prefix is not None, "prefix caching is not enabled"
        ids = self.prefix.match(prompt, n_prompt)
        h = len(ids)
        self.prefix.acquire(ids)
        self._shared[slot] = set(ids)
        if h:
            self.table[slot, :h] = ids
        self._reserved[slot] = self._need_blocks(n_prompt, budget) - h
        self.prefix_lookups += 1
        self.prefix_lookup_tokens += int(n_prompt)
        if h:
            self.prefix_hits += 1
            self.prefix_hit_tokens += h * self.block_size
        return h * self.block_size

    def register_prefix(self, slot: int, prompt, n_prompt: int) -> None:
        """Publish the slot's full prompt blocks as shared. Called once
        the prompt's KV is fully resident. Only blocks wholly inside
        the prompt are shareable — every later write (decode, verify)
        lands at position ``>= n_prompt``, i.e. in a later, privately
        allocated block, so published blocks are immutable (this is the
        copy-on-write guarantee). Hashes already mapped to a different
        canonical block are skipped: the slot keeps its private copy."""
        if self.prefix is None:
            return
        full = int(n_prompt) // self.block_size
        if not full:
            return
        keys = self.prefix.keys_for(prompt, full)
        for k in range(full):
            blk = int(self.table[slot, k])
            if blk in self._shared[slot]:
                continue  # already aliased shared (a match hit)
            if self.prefix.register(keys[k], blk):
                self._shared[slot].add(blk)

    def reserve(self, slot: int, n_prompt: int, budget: int) -> None:
        """Chunked admission: hold the request's whole worst-case block
        count before any chunk lands. Chunks then allocate lazily
        (:meth:`splice_partial` charges only the blocks each chunk
        actually touches, paying the reservation down) — resident bytes
        grow per chunk, while the *reservation* keeps later admissions
        from eating blocks this request still needs mid-prefill or
        mid-decode (the same no-deadlock invariant as blocking
        admission)."""
        self._reserved[slot] = self._need_blocks(n_prompt, budget)

    def splice_partial(self, k_rows, v_rows, slot: int, offset: int,
                       n_valid: int) -> None:
        bs = self.block_size
        for b in range(offset // bs,
                       math.ceil((offset + n_valid) / bs)):
            if self.table[slot, b] == self.num_blocks:
                self.table[slot, b] = self._alloc_block()
                self._reserved[slot] = max(0, int(self._reserved[slot]) - 1)
        s = int(k_rows.shape[2])
        pos = offset + np.arange(s)
        blk = np.full(s, self.num_blocks, np.int32)
        valid = np.arange(s) < n_valid
        blk[valid] = self.table[slot, pos[valid] // bs]
        self._pool_k, self._pool_v = splice_positions(
            self._pool_k, self._pool_v, k_rows, v_rows,
            jnp.asarray(blk), jnp.asarray(pos % bs, np.int32))

    def chunk_view(self, slot: int) -> dict:
        return {"kind": "paged", "k": self._pool_k, "v": self._pool_v,
                "table": jnp.asarray(self.table[slot])}

    def decode_view(self, pos, live) -> dict:
        return self.verify_view(pos, live, np.ones(len(self.table),
                                                   np.int32))

    def verify_view(self, pos, live, n_tokens) -> dict:
        """Allocate every block the verify window ``pos[i] .. pos[i] +
        n_tokens[i] - 1`` touches (``n_tokens`` is the row's commit
        cap — bounded by its generation budget, which the admission
        reservation already covers, so these allocations pay the
        reservation down and can never exhaust the pool). Candidate
        positions past the cap have no block; the dispatch drops those
        writes via the sentinel table entry."""
        bs = self.block_size
        for i in np.nonzero(live)[0]:
            last = min(int(pos[i]) + max(int(n_tokens[i]), 1) - 1,
                       self._max_seq_len - 2)
            for b in range(int(pos[i]) // bs, last // bs + 1):
                if self.table[i, b] == self.num_blocks:
                    self.table[i, b] = self._alloc_block()
                    self._reserved[i] = max(0, int(self._reserved[i]) - 1)
        return {"k": self._pool_k, "v": self._pool_v,
                "block_tab": jnp.asarray(self.table),
                "len": jnp.zeros((), jnp.int32)}

    def commit_n(self, slot: int, n_valid: int) -> None:
        """Speculative rollback: the slot's KV is valid only to
        position ``n_valid - 1``; free every allocated block wholly
        past it and put the capacity back on the reservation (those
        positions may still be written later — the worst-case admission
        bound must keep covering them or a later verify could deadlock
        the pool)."""
        keep = max(1, math.ceil(n_valid / self.block_size))
        for b in range(keep, self.table_width):
            blk = int(self.table[slot, b])
            if blk == self.num_blocks:
                # lazy allocation fills a slot's table as a contiguous
                # prefix (splice from 0, decode/verify at the write
                # head, commit_n frees a suffix), so the first sentinel
                # ends the scan — O(freed) host work, not O(width)
                break
            self._free_block(blk)
            self.table[slot, b] = self.num_blocks
            self._reserved[slot] += 1

    def commit(self, new_cache: dict) -> None:
        self._pool_k = new_cache["k"]
        self._pool_v = new_cache["v"]

    def free(self, slot: int) -> None:
        shared = self._shared[slot]
        for blk in self.table[slot]:
            if blk == self.num_blocks:
                continue
            blk = int(blk)
            if blk in shared:
                # shared blocks are released, never raw-freed: at
                # refcount zero they stay resident on the LRU queue
                self.prefix.release(blk)
            else:
                self._free_block(blk)
        self.table[slot] = self.num_blocks
        self._reserved[slot] = 0
        self._shared[slot] = set()

    def export_slot(self, slot: int, n_valid: int, prompt=None,
                    n_prompt=None) -> dict:
        """Block-table-aware pack: gather the slot's allocated blocks
        (lazy allocation fills them as a contiguous prefix, so the
        first ``ceil(n_valid / bs)`` table entries are all real) into
        dense per-layer rows — the backend-portable handoff format.

        With prefix caching on and the request's prompt supplied, the
        packet carries shared-block provenance (the prompt token stream
        plus ``n_prompt``): a prefix-enabled importer re-matches it
        against *its own* index and aliases whatever it already holds
        instead of allocating private copies — migration and preemption
        stay refcount-correct on both ends (the exporter's aliases are
        released by :meth:`free`, never raw-freed)."""
        bs = self.block_size
        nblk = max(1, math.ceil(max(int(n_valid), 1) / bs))
        idx = jnp.asarray(self.table[slot, :nblk], jnp.int32)
        k = gather_blocks(self._pool_k, idx)
        v = gather_blocks(self._pool_v, idx)
        packet = {"n_valid": int(n_valid),
                  "k": np.asarray(jax.device_get(k)),
                  "v": np.asarray(jax.device_get(v))}
        packet["kv_bytes"] = packet["k"].nbytes + packet["v"].nbytes
        if (self.prefix is not None and prompt is not None
                and n_prompt is not None):
            packet["prefix"] = {
                "tokens": np.asarray(prompt, np.int32).copy(),
                "n_prompt": int(n_prompt),
                "shared_blocks": len(self._shared[slot]),
            }
        return packet

    def import_slot(self, packet: dict, slot: int, n_prompt: int,
                    budget: int) -> None:
        """Unpack into freshly allocated blocks and re-run the
        reservation math: the request's worst case (``n_prompt`` +
        ``budget``, the same bound blocking admission charges) minus
        the blocks allocated now stays reserved, so the migrated
        request keeps the no-mid-decode-deadlock guarantee on the
        importing pool. Callers gate on :meth:`can_admit` first."""
        if (self.table[slot] != self.num_blocks).any() or self._reserved[slot]:
            raise RuntimeError(
                f"import_slot into occupied slot {slot}: its block-table "
                "row still holds allocated blocks (or a live "
                "reservation) that would leak from the pool — free the "
                "slot first (preemption/requeue must never double-import)")
        bs = self.block_size
        n_valid = int(packet["n_valid"])
        now = max(1, math.ceil(max(n_valid, 1) / bs))
        need = self._need_blocks(n_prompt, budget)
        # shared-block provenance: re-match the prompt against our own
        # index and alias the cached prefix instead of copying it in —
        # private blocks (and the packet's dense rows) cover only the
        # tail. A resumed/migrated request thus re-joins the shared
        # prefix wherever the importer already holds it. h < now always:
        # matches stop at (n_prompt - 1) // bs and n_valid >= n_prompt.
        ids: list[int] = []
        prov = packet.get("prefix")
        if self.prefix is not None and prov is not None:
            ids = self.prefix.match(prov["tokens"], int(prov["n_prompt"]))
        h = len(ids)
        if ids:
            self.prefix.acquire(ids)
        self._shared[slot] = set(ids)
        if h:
            self.table[slot, :h] = ids
        blocks = [self._alloc_block() for _ in range(now - h)]
        self.table[slot, h:now] = blocks
        self._reserved[slot] = max(0, need - now)
        span = now * bs
        rows_k, rows_v = packet["k"], packet["v"]
        if rows_k.shape[2] < span:  # cross-backend: re-quantize the span
            pad = [(0, 0)] * rows_k.ndim
            pad[2] = (0, span - rows_k.shape[2])
            rows_k = np.pad(rows_k, pad)
            rows_v = np.pad(rows_v, pad)
        self._pool_k, self._pool_v = splice_blocks(
            self._pool_k, self._pool_v,
            jnp.asarray(rows_k[:, :, h * bs:span]),
            jnp.asarray(rows_v[:, :, h * bs:span]),
            jnp.asarray(blocks, jnp.int32))

    def resident_kv_bytes(self) -> int:
        return (self.allocator.allocated_blocks * self.block_size
                * self._bytes_per_token)

    @property
    def peak_resident_kv_bytes(self) -> int:
        return (self.allocator.peak_allocated * self.block_size
                * self._bytes_per_token)

    @property
    def resident_shared_kv_bytes(self) -> int:
        """Bytes held by blocks the prefix index has published (any
        refcount, including the refcount-zero LRU tail)."""
        if self.prefix is None:
            return 0
        return (self.prefix.resident_blocks * self.block_size
                * self._bytes_per_token)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from the cache."""
        if not self.prefix_lookup_tokens:
            return 0.0
        return self.prefix_hit_tokens / self.prefix_lookup_tokens


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------

def make_kv_cache(cfg, ecfg, mesh=None) -> KVCacheManager:
    """Build the configured backend; families the paged layout cannot
    express (recurrent state, rolling SWA) fall back to contiguous.
    ``mesh`` (a ``jax.sharding.Mesh`` with ``data``/``model`` axes)
    places the resident pool sharded — batch/heads for contiguous,
    heads-only for paged — next to the engine's sharded dispatches."""
    kind = getattr(ecfg, "kv_cache", "contiguous")
    if kind == "contiguous":
        return ContiguousCache(cfg, ecfg, mesh=mesh)
    if kind == "paged":
        if (cfg.family not in MD.TRANSFORMER_FAMILIES
                or cfg.sliding_window is not None):
            warnings.warn(
                f"paged KV cache unsupported for family={cfg.family!r} "
                f"sliding_window={cfg.sliding_window}; falling back to "
                "contiguous", stacklevel=2)
            return ContiguousCache(cfg, ecfg, mesh=mesh)
        return PagedCache(cfg, ecfg, mesh=mesh)
    raise ValueError(f"unknown kv_cache backend {kind!r}")
