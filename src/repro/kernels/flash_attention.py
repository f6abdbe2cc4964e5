"""Pallas TPU flash attention (prefill/training path).

TPU adaptation of the paper's "process where the data lives" insight:
each K/V tile is streamed HBM->VMEM exactly once per query block, the
S x S score matrix never exists in HBM, and the online-softmax state
(m, l, acc) lives in VMEM scratch across the sequential innermost grid
dimension (TPU grids iterate the last axis fastest on-core).

Grid: (B*H, num_q_blocks, num_kv_blocks); BlockSpecs tile q/k/v/o to
(block_q|block_k, d_head) VMEM tiles. Causal/window masking uses
absolute positions (``q_offset`` supports continuation prefill), and
out-of-range KV blocks are skipped entirely with ``pl.when`` — the
block-sparsity that keeps SWA prefill linear.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, block_q, block_k, seq_q, seq_k, causal, window,
            q_offset):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this tile
    q_pos = q_offset + qb * block_q + jax.lax.iota(jnp.int32, block_q)
    k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)

    # static-shape dynamic visibility: skip fully-masked KV blocks
    q_lo = q_offset + qb * block_q
    q_hi = q_lo + block_q - 1
    k_lo = kb * block_k
    k_hi = k_lo + block_k - 1
    visible = jnp.asarray(True)
    if causal:
        visible = jnp.logical_and(visible, k_lo <= q_hi)
    if window is not None:
        visible = jnp.logical_and(visible, k_hi > q_lo - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, dh)
        k = k_ref[0]                                       # (bk, dh)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bq, bk)
        ok = k_pos[None, :] < seq_k                        # pad mask
        if causal:
            ok = jnp.logical_and(ok, k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            ok = jnp.logical_and(ok, k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=-1))[:, None]
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bq, dh)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _hist_kernel(len_ref, q_ref, kh_ref, vh_ref, ks_ref, vs_ref, o_ref,
                 m_ref, l_ref, acc_ref, *, scale, block_q, block_k,
                 nk_hist):
    """Chunked-prefill kernel body: one softmax over (cached history +
    chunk self) KV. The innermost grid axis walks the history blocks
    first, then the chunk's own blocks; the per-row ``hist_len`` scalar
    (prefetched, like the split-KV decode kernel's length vector) masks
    the unwritten history tail, while within-chunk masking is plain
    causality in chunk-relative coordinates — independent of the
    (dynamic) history length, so the block skip for the self region
    stays static. The two phases are separate ``pl.when`` regions, each
    reading its own operands: Mosaic cannot lower a select between
    whole operand tiles."""
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    hist_len = len_ref[pl.program_id(0)]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def accumulate(k_ref, v_ref, ok):
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, dh)
        s = jax.lax.dot_general(
            q, k_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=-1))[:, None]
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, dh)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    # history block: any position < hist_len
    @pl.when(jnp.logical_and(kb < nk_hist, kb * block_k < hist_len))
    def _history():
        accumulate(kh_ref, vh_ref, kb * block_k + cols < hist_len)

    # self block: causal reach in chunk-relative coordinates
    ks0 = (kb - nk_hist) * block_k
    q0 = qb * block_q

    @pl.when(jnp.logical_and(kb >= nk_hist, ks0 <= q0 + block_q - 1))
    def _self():
        accumulate(ks_ref, vs_ref, ks0 + cols <= q0 + rows)

    @pl.when(kb == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_hist_bhsd(q, k_hist, v_hist, k_self, v_self, hist_len,
                              *, block_q=128, block_k=128, interpret=None):
    """Prefill-over-cache: q (BH, S, Dh) at absolute positions
    ``hist_len + 0..S-1`` attends ``k_hist``/``v_hist`` (BH, C, Dh)
    masked to the first ``hist_len`` rows (scalar or per-row (BH,)
    int32) plus its own causal ``k_self``/``v_self`` (BH, S, Dh).
    One online softmax spans both — the history side streams exactly
    like the split-KV decode kernel (per-row length prefetch), the self
    side like the training flash kernel.

    Two callers share this kernel: chunked prefill (S ~ chunk_tokens,
    per-row length optional) and the speculative **multi-token verify**
    step (S = gamma + 1, a handful of candidate tokens per row, per-row
    lengths mandatory — every serving slot verifies at its own
    absolute position). The KV tile size therefore follows the larger
    of the two streamed extents: clamping it to the tiny verify-side S
    (the old ``min(c, sq)``) would shred a long history into 8-position
    DMAs and make verify slower than the gamma single-token dispatches
    it replaces."""
    bh, sq, dh = q.shape
    c = k_hist.shape[1]
    block_q = min(block_q, max(8, sq))
    block_k = min(block_k, max(8, c, sq))
    nq = math.ceil(sq / block_q)
    nk_h = math.ceil(c / block_k)
    nk_s = math.ceil(sq / block_k)
    sq_p = nq * block_q
    sk_hp = nk_h * block_k
    sk_sp = nk_s * block_k
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_hp != c:
        k_hist = jnp.pad(k_hist, ((0, 0), (0, sk_hp - c), (0, 0)))
        v_hist = jnp.pad(v_hist, ((0, 0), (0, sk_hp - c), (0, 0)))
    if sk_sp != sq:
        k_self = jnp.pad(k_self, ((0, 0), (0, sk_sp - sq), (0, 0)))
        v_self = jnp.pad(v_self, ((0, 0), (0, sk_sp - sq), (0, 0)))

    kernel = functools.partial(
        _hist_kernel, scale=1.0 / math.sqrt(dh), block_q=block_q,
        block_k=block_k, nk_hist=nk_h)
    # Index maps clamp the "other phase" operand to a constant block
    # (hist pins at nk_h-1 through the self phase, self pins at 0
    # through the history phase), so the TPU pipeline re-DMAs the
    # unused operand only at the single phase boundary, not per step.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, nk_h + nk_s),
        in_specs=[
            pl.BlockSpec((1, block_q, dh),
                         lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda b, i, j, *_: (b, jnp.minimum(j, nk_h - 1),
                                              0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda b, i, j, *_: (b, jnp.minimum(j, nk_h - 1),
                                              0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda b, i, j, *_: (b, jnp.maximum(j - nk_h, 0),
                                              0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda b, i, j, *_: (b, jnp.maximum(j - nk_h, 0),
                                              0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
    )
    lens = jnp.broadcast_to(
        jnp.asarray(hist_len, jnp.int32).reshape(-1), (bh,))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, dh), q.dtype),
        interpret=interpret_mode(interpret), name="prefill_attention",
    )(lens, q, k_hist, v_hist, k_self, v_self)
    return out[:, :sq]


def flash_attention_bhsd(q, k, v, *, causal=True, window=None, q_offset=0,
                         block_q=128, block_k=128, interpret=None):
    """q (BH, Sq, Dh); k, v (BH, Skv, Dh) — heads pre-expanded/merged."""
    bh, sq, dh = q.shape
    sk = k.shape[1]
    block_q = min(block_q, max(8, sq))
    block_k = min(block_k, max(8, sk))
    nq = math.ceil(sq / block_q)
    nk = math.ceil(sk / block_k)
    sq_p, sk_p = nq * block_q, nk * block_k
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(dh), block_q=block_q,
        block_k=block_k, seq_q=sq, seq_k=sk, causal=causal, window=window,
        q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret_mode(interpret), name="flash_attention",
    )(q, k, v)
    return out[:, :sq]
