"""Pallas TPU int4 dequant-in-register GEMV/GEMM (W4A16).

The paper's mobile mode stores weights in 4-bit and computes in 16-bit
(§3.4). On TPU the win is identical to PIM's: decode is weight-
bandwidth-bound, so halving/quartering the streamed weight bytes scales
tokens/s almost linearly. This kernel streams nibble-packed int4 weight
tiles HBM->VMEM, unpacks + dequantizes in registers (never materializing
the fp16 weight matrix in HBM), and accumulates the GEMV in fp32 VMEM
scratch.

Layout: w_packed (K//2, N) uint8 — row 2k in the low nibble, row 2k+1 in
the high nibble; symmetric per-(group x column) scales (K//group, N).
The K block size equals ``group`` so each grid step consumes exactly one
scale row.

Grid: (num_n_blocks, num_k_blocks) — K innermost (sequential
accumulation in scratch).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, group):
    kb = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = w_ref[...].astype(jnp.int32)     # (group//2, bn)
    lo = packed & 0xF                         # rows 2i of the group
    hi = (packed >> 4) & 0xF                  # rows 2i+1
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    # [even rows; odd rows] — a sublane-aligned concat, matched by the
    # wrapper's per-group [even; odd] reordering of x (an in-register
    # row interleave does not lower on TPU)
    w = jnp.concatenate([lo, hi], axis=0).astype(jnp.float32)
    w = w * s_ref[0].astype(jnp.float32)      # (group, bn) * (1, bn)
    x = x_ref[...].astype(jnp.float32)        # (B, group), [even; odd]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_gemv(x, w_packed, scales, *, group=128, block_n=256,
               interpret=None):
    """x (B, K) bf16/f32; w_packed (K//2, N) uint8; scales (K//group, N).
    Returns (B, N) in x.dtype."""
    b, k = x.shape
    kp, n = w_packed.shape
    assert kp * 2 == k, (kp, k)
    assert k % group == 0
    nk = k // group
    block_n = min(block_n, n)
    nn = math.ceil(n / block_n)
    n_p = nn * block_n
    if n_p != n:
        w_packed = jnp.pad(w_packed, ((0, 0), (0, n_p - n)))
        scales = jnp.pad(scales, ((0, 0), (0, n_p - n)))

    kernel = functools.partial(_kernel, group=group)
    out = pl.pallas_call(
        kernel,
        grid=(nn, nk),
        in_specs=[
            pl.BlockSpec((b, group), lambda ni, ki: (0, ki)),
            pl.BlockSpec((group // 2, block_n), lambda ni, ki: (ki, ni)),
            # scales ride as (K//group, 1, N): a one-row block over a
            # unit middle dim satisfies Mosaic's (8, 128) tiling rule
            pl.BlockSpec((1, 1, block_n), lambda ni, ki: (ki, 0, ni)),
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda ni, ki: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((b, n_p), x.dtype),
        scratch_shapes=[pltpu.VMEM((b, block_n), jnp.float32)],
        interpret=interpret_mode(interpret), name="quant_gemv",
    )(x.reshape(b, nk, group // 2, 2).swapaxes(2, 3).reshape(b, k),
      w_packed, scales[:, None, :])
    return out[:, :n]
