"""Shared pure-JAX building blocks: inits, norms, MLPs, RoPE, embeddings.

Params are plain nested dicts of jnp arrays. Layer stacks carry a leading
``L`` dimension on every leaf so model bodies run under ``lax.scan``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed import hints

Params = dict


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _normal(key, shape):
    """Float32 standard normals. The barrier keeps a jitted init from
    folding the caller's scale into the sampler's own constants, so
    jitted and eager inits draw bit-identical weights."""
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))


def dense_init(key, shape, dtype, fan_in: int | None = None):
    fan = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / math.sqrt(max(1, fan))
    return (_normal(key, shape) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (_normal(key, shape) * 0.02).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def init_norm(key, cfg, d=None):
    d = d or cfg.d_model
    dt = dtype_of(cfg)
    if cfg.norm == "rmsnorm":
        return {"w": jnp.ones((d,), dt)}
    return {"w": jnp.ones((d,), dt), "b": jnp.zeros((d,), dt)}


def apply_norm(p, cfg, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["w"])
    return layernorm(x, p["w"], p["b"])


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff: int | None = None):
    d, dt = cfg.d_model, dtype_of(cfg)
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d, f), dt),
            "w_up": dense_init(ks[1], (d, f), dt),
            "w_down": dense_init(ks[2], (f, d), dt, fan_in=f),
        }
    return {
        "w_up": dense_init(ks[0], (d, f), dt),
        "w_down": dense_init(ks[1], (f, d), dt, fan_in=f),
    }


def apply_mlp(p, cfg, x):
    # bitwise serving: pin the MLP entry as well as the w_down input —
    # with the slot batch live on the ``data`` axis (KV cache), GSPMD
    # otherwise batch-splits the up-projections onto the free axis and
    # the local gemm's accumulation order drifts from single-device
    x = hints.row_input(x)
    act = cfg.activation
    if act in ("swiglu", "geglu"):
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        u = jnp.einsum("...d,df->...f", x, p["w_up"])
        g = jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)
        h = g * u
    else:
        h = jnp.einsum("...d,df->...f", x, p["w_up"])
        if act == "gelu":
            h = jax.nn.gelu(h)
        elif act == "squared_relu":
            r = jax.nn.relu(h)
            h = r * r
        else:
            raise ValueError(f"unknown activation {act}")
    return jnp.einsum("...f,fd->...d", hints.row_input(h), p["w_down"])


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float):
    exponent = jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head
    return 1.0 / (theta ** exponent)  # (d_head/2,)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, Dh); positions: (..., S) int32."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)  # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, Dh/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, d_model: int):
    """Classic transformer sinusoid table computed on the fly."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------

def init_embedding(key, cfg):
    return {"table": embed_init(key, (cfg.vocab_size, cfg.d_model), dtype_of(cfg))}


def embed_tokens(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


@jax.named_scope("logits")
def logits_from_hidden(head_table, x):
    """head_table: (V, d). Returns fp32 logits."""
    return jnp.einsum(
        "...d,vd->...v", x, head_table, preferred_element_type=jnp.float32
    )


def softmax_cross_entropy(logits, labels, mask=None):
    """logits fp32 (..., V), labels int (...,). Mean over unmasked."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
