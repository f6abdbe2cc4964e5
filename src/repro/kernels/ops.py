"""Jitted public wrappers for the Pallas kernels.

Each kernel runs compiled on a TPU backend and in the Pallas interpreter
(bit-accurate against the BlockSpec tiling) everywhere else — decided
once, by :func:`repro.kernels.interpret_mode`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import quant_gemv as _qg
from repro.kernels import rmsnorm as _rn
from repro.kernels.ref import quantize_int4, pack_int4  # noqa: F401


@partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                   "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    block_q=128, block_k=128):
    """q (B,Sq,Hq,Dh); k,v (B,Skv,Hkv,Dh). GQA is expanded to Hq."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    qm = jnp.moveaxis(q, 2, 1).reshape(b * hq, sq, dh)
    km = jnp.moveaxis(k, 2, 1).reshape(b * hq, -1, dh)
    vm = jnp.moveaxis(v, 2, 1).reshape(b * hq, -1, dh)
    o = _fa.flash_attention_bhsd(qm, km, vm, causal=causal, window=window,
                                 q_offset=q_offset, block_q=block_q,
                                 block_k=block_k)
    return jnp.moveaxis(o.reshape(b, hq, sq, dh), 1, 2)


@partial(jax.jit, static_argnames=("block_s",))
def decode_attention(q, k_cache, v_cache, cache_len, *, block_s=256):
    """q (B,1,Hq,Dh); caches (B,S,Hkv,Dh). Split-KV GQA flash decode.
    ``cache_len``: scalar, or per-row (B,) int32 for ragged batches."""
    b, _, hq, dh = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, dh)
    o = _dec.decode_attention_bhgd(qg, k_cache, v_cache, cache_len,
                                   block_s=block_s)
    return o.reshape(b, 1, hq, dh)


@jax.jit
def prefill_attention(q, k_hist, v_hist, hist_len, k_self, v_self):
    """Chunked-prefill entry point: q (B,S,Hq,Dh) at absolute positions
    ``hist_len..hist_len+S-1`` attends the cached history
    ``k_hist``/``v_hist`` (B,C,Hkv,Dh), valid to ``hist_len`` (scalar
    or per-row (B,)), plus its own causal ``k_self``/``v_self``
    (B,S,Hkv,Dh). GQA is expanded to Hq and heads merged into the
    leading dim, exactly like :func:`flash_attention`."""
    b, sq, hq, dh = q.shape
    hkv = k_hist.shape[2]
    if hkv != hq:
        rep = hq // hkv
        k_hist = jnp.repeat(k_hist, rep, axis=2)
        v_hist = jnp.repeat(v_hist, rep, axis=2)
        k_self = jnp.repeat(k_self, rep, axis=2)
        v_self = jnp.repeat(v_self, rep, axis=2)
    qm = jnp.moveaxis(q, 2, 1).reshape(b * hq, sq, dh)
    khm = jnp.moveaxis(k_hist, 2, 1).reshape(b * hq, -1, dh)
    vhm = jnp.moveaxis(v_hist, 2, 1).reshape(b * hq, -1, dh)
    ksm = jnp.moveaxis(k_self, 2, 1).reshape(b * hq, sq, dh)
    vsm = jnp.moveaxis(v_self, 2, 1).reshape(b * hq, sq, dh)
    lens = jnp.broadcast_to(
        jnp.asarray(hist_len, jnp.int32).reshape(-1, 1), (b, hq)
    ).reshape(b * hq)
    o = _fa.flash_attention_hist_bhsd(qm, khm, vhm, ksm, vsm, lens)
    return jnp.moveaxis(o.reshape(b, hq, sq, dh), 1, 2)


def verify_attention(q, k_hist, v_hist, hist_len, k_self, v_self):
    """Speculative-verify entry point: q (B, S, Hq, Dh) holds each
    row's ``S = gamma + 1`` candidate tokens at absolute positions
    ``hist_len[b] .. hist_len[b] + S - 1``; ``hist_len`` is the
    **per-row** (B,) valid-history length (scalar accepted and
    broadcast), prefetched like the split-KV decode kernel's length
    vector so one dispatch verifies a fully-ragged batch of candidate
    windows. ``k_hist``/``v_hist`` (B, C, Hkv, Dh) are the rows'
    cached KV, ``k_self``/``v_self`` (B, S, Hkv, Dh) the candidates'
    own KV (causal within the window).

    This is :func:`prefill_attention` generalized down to tiny S — the
    same ``flash_attention_hist_bhsd`` kernel, whose KV tile size
    follows the history extent rather than S — and ``S = 1``
    degenerates to the split-KV decode kernel's semantics (one softmax
    over history + the single always-visible self slot)."""
    return prefill_attention(q, k_hist, v_hist, hist_len, k_self, v_self)


@jax.jit
def paged_decode_attention(q, k_pool, v_pool, block_tables, cache_len):
    """q (B,1,Hq,Dh); pools (NB,bs,Hkv,Dh); block_tables (B,W) int32.
    Split-KV GQA flash decode over a paged (block-table) KV cache — one
    streamed pool block per grid step, no dense gather."""
    b, _, hq, dh = q.shape
    hkv = k_pool.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, dh)
    o = _dec.decode_attention_paged_bhgd(qg, k_pool, v_pool, block_tables,
                                         cache_len)
    return o.reshape(b, 1, hq, dh)


@partial(jax.jit, static_argnames=("group", "block_n"))
def quant_gemv(x, w_packed, scales, *, group=128, block_n=256):
    return _qg.quant_gemv(x, w_packed, scales, group=group,
                          block_n=block_n)


@partial(jax.jit, static_argnames=("eps", "block_m"))
def rmsnorm(x, w, *, eps=1e-6, block_m=8):
    return _rn.rmsnorm(x, w, eps=eps, block_m=block_m)
