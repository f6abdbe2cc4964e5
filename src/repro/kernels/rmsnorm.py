"""Pallas TPU fused RMSNorm.

One HBM read + one HBM write per element: the mean-square reduction,
rsqrt and scale all happen on the VMEM-resident tile (the unfused jnp
version reads x twice and round-trips the normalized intermediate).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                    # (bm, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)[None, :]
                  ).astype(o_ref.dtype)


def rmsnorm(x, w, *, eps=1e-6, block_m=8, interpret=None):
    """x (..., d); w (d,). Row-tiled fused RMSNorm."""
    orig_shape = x.shape
    d = x.shape[-1]
    xm = x.reshape(-1, d)
    m = xm.shape[0]
    block_m = min(block_m, m)
    nm = math.ceil(m / block_m)
    m_p = nm * block_m
    if m_p != m:
        xm = jnp.pad(xm, ((0, m_p - m), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(nm,),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_p, d), x.dtype),
        interpret=interpret_mode(interpret), name="rmsnorm",
    )(xm, w)
    return out[:m].reshape(orig_shape)
