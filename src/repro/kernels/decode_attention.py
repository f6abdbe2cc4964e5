"""Pallas TPU split-KV flash decode — the paper's memory-bound GEMV
hot-spot, adapted to the TPU memory hierarchy.

Decode attention reads the whole KV cache once per generated token; on
PIM hardware that read happens next to the DRAM banks, on TPU the best
we can do is stream each KV tile HBM->VMEM exactly once and never spill
intermediates. The sequence axis is split across the innermost
(sequential) grid dimension with online-softmax state in VMEM scratch —
the TPU analogue of the paper's bank-parallel split — and all G query
heads of one KV head share each streamed tile (the GQA amplification
that PIM-AI's capacity argument is about).

Grid: (B, Hkv / heads-per-block, num_s_blocks); the cache lengths
arrive as a per-row (B,) scalar-prefetch vector so each batch row masks
its own valid KV span — the fully-ragged continuous-batching case where every serving
slot sits at a different absolute position — without the host slicing
the cache or splitting the batch into position groups. A scalar
``cache_len`` is accepted too (broadcast to all rows).

Paged variant: :func:`decode_attention_paged_bhgd` reads KV from a
shared block pool (NB, bs, Hkv, Dh) through per-row block tables
(B, W) — the vLLM-style layout where each serving slot holds only the
blocks it has actually written. The block table is a *second*
scalar-prefetch operand, so the K/V BlockSpec index maps dereference
``tab[b, w]`` before the DMA is issued: the kernel streams exactly the
row's own blocks HBM->VMEM, never a gathered dense copy. Sentinel
(unallocated) table entries are clamped onto the last pool block and
masked off by ``cache_len`` — identical to how the unwritten tail of a
contiguous cache is masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _head_block(hkv: int) -> int:
    """KV heads per grid step. A block's last two dims are (heads, Dh):
    Mosaic needs the heads dim to be a multiple of 8 or the whole head
    axis (Dh is always whole), so 8 heads ride together when they
    divide, and small head counts take the full axis."""
    return 8 if hkv % 8 == 0 else hkv


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, block_s):
    # q/o (1, G, hb, Dh); k/v (1, block_s, hb, Dh); m/l (G, hb, 1) and
    # acc (G, hb, Dh) hold the online-softmax state of every head in
    # the block. Scores are lane reductions over Dh (VPU) rather than
    # per-head matmuls: decode is bandwidth-bound, and this keeps the
    # streamed (positions, heads, Dh) tile in its HBM layout.
    sb = pl.program_id(2)
    ns = pl.num_programs(2)
    cache_len = len_ref[pl.program_id(0)]  # this row's valid KV span

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(sb * block_s < cache_len)
    def _compute():
        k = k_ref[0].astype(jnp.float32)                  # (bs, hb, dh)
        v = v_ref[0].astype(jnp.float32)
        pos = sb * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (block_s, k.shape[1], 1), 0)
        valid = pos < cache_len                           # (bs, hb, 1)
        for g in range(q_ref.shape[1]):
            q = q_ref[0, g].astype(jnp.float32) * scale   # (hb, dh)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)
            s = jnp.where(valid, s, NEG_INF)              # (bs, hb, 1)
            m_prev = m_ref[g]                             # (hb, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[None])
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=0)
            acc_ref[g] = acc_ref[g] * alpha + jnp.sum(p * v, axis=0)
            m_ref[g] = m_new

    @pl.when(sb == ns - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _scratch(g, hb, dh):
    return [pltpu.VMEM((g, hb, 1), jnp.float32),
            pltpu.VMEM((g, hb, 1), jnp.float32),
            pltpu.VMEM((g, hb, dh), jnp.float32)]


def decode_attention_bhgd(q, k_cache, v_cache, cache_len, *, block_s=256,
                          interpret=None):
    """q (B, Hkv, G, Dh); caches (B, S, Hkv, Dh); cache_len scalar or
    per-row (B,) int32 valid-KV lengths. Returns (B, Hkv, G, Dh)."""
    b, hkv, g, dh = q.shape
    s = k_cache.shape[1]
    hb = _head_block(hkv)
    block_s = min(block_s, max(8, s))
    ns = math.ceil(s / block_s)
    s_p = ns * block_s
    if s_p != s:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, s_p - s), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, s_p - s), (0, 0), (0, 0)))

    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(dh),
                               block_s=block_s)
    qo_spec = pl.BlockSpec((1, g, hb, dh),
                           lambda bi, h, si, *_: (bi, 0, h, 0))
    kv_spec = pl.BlockSpec((1, block_s, hb, dh),
                           lambda bi, h, si, *_: (bi, si, h, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv // hb, ns),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=_scratch(g, hb, dh),
    )
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, hkv, dh), q.dtype),
        interpret=interpret_mode(interpret), name="decode_attention",
    )(lens, jnp.swapaxes(q, 1, 2), k_cache, v_cache)
    return jnp.swapaxes(out, 1, 2)


def _paged_kernel(len_ref, tab_ref, *rest, **kw):
    # the block table is consumed by the BlockSpec index maps (it steers
    # which pool block each grid step DMAs); the body itself is the same
    # online-softmax accumulation as the contiguous kernel.
    return _kernel(len_ref, *rest, **kw)


def decode_attention_paged_bhgd(q, k_pool, v_pool, block_tables, cache_len,
                                *, interpret=None):
    """Paged split-KV flash decode.

    q (B, Hkv, G, Dh); ``k_pool``/``v_pool`` (NB, bs, Hkv, Dh) shared
    block pools; ``block_tables`` (B, W) int32 per-row block ids (their
    concatenation is the row's logical KV span, entries >= NB are
    unallocated sentinels); ``cache_len`` scalar or per-row (B,) valid
    lengths. One grid step streams one pool block — the KV tile size is
    the cache block size, so paging never re-reads or densifies the
    pool. Returns (B, Hkv, G, Dh).
    """
    b, hkv, g, dh = q.shape
    nb, bs, _, _ = k_pool.shape
    w = block_tables.shape[1]
    hb = _head_block(hkv)
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(dh),
                               block_s=bs)
    qo_spec = pl.BlockSpec((1, g, hb, dh),
                           lambda bi, h, wi, *_: (bi, 0, h, 0))
    kv_spec = pl.BlockSpec((1, bs, hb, dh),
                           lambda bi, h, wi, lens, tab:
                           (jnp.minimum(tab[bi, wi], nb - 1), 0, h, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv // hb, w),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=_scratch(g, hb, dh),
    )
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, hkv, dh), q.dtype),
        interpret=interpret_mode(interpret), name="paged_decode_attention",
    )(lens, jnp.asarray(block_tables, jnp.int32), jnp.swapaxes(q, 1, 2),
      k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2)
