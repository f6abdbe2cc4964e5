"""Model assembly for every assigned architecture family.

One functional API over all ten architectures (plus the paper's own
models):

- ``init_params(key, cfg)``      -> params pytree (layer-stacked leaves)
- ``forward(params, cfg, batch)``-> full-sequence logits (train path)
- ``loss_fn(params, cfg, batch)``-> (scalar loss, metrics)
- ``init_cache(cfg, B, capacity)``-> decode cache pytree (zeros)
- ``prefill(params, cfg, batch, capacity)`` -> (last-token logits, cache)
- ``decode_step(params, cfg, tokens, cache)`` -> (logits, cache)

Families:
- dense / moe / vlm: decoder-only transformer (GQA/MHA/SWA + RoPE), MoE
  FFN where configured, stub patch-embedding prefix for vlm.
- audio: encoder-decoder (Whisper backbone) with a stub frame-embedding
  frontend; decoder carries self-attn KV + fixed cross-attn KV.
- ssm: xLSTM (mLSTM chunkwise + sLSTM sequential), O(1)/token decode.
- hybrid: Mamba2 backbone + one *shared* attention+MLP block applied
  every ``attn_every`` layers (Zamba2), linear-KV + O(1)-state decode.

All layer stacks carry a leading L dim and run under ``lax.scan``; the
block body is wrapped in ``jax.checkpoint`` when ``cfg.remat != 'none'``
(policy ``'dots'`` keeps dot outputs, ``'full'`` recomputes everything).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.attention import (attention, decode_attention,
                                    gather_kv_blocks, prefill_over_cache)
from repro.distributed import hints

TRANSFORMER_FAMILIES = ("dense", "moe", "vlm")
RECURRENT_FAMILIES = ("ssm", "hybrid")  # state-based decode; prefill
                                        # buckets via length-masked scan


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _stack_init(init_fn, key, n):
    """vmap an init over n split keys -> leading-L stacked params."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# attention block (projections live here; math lives in attention.py)
# ---------------------------------------------------------------------------

def init_attn(key, cfg):
    d, dt = cfg.d_model, L.dtype_of(cfg)
    hq, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (d, hq * dh), dt),
        "wk": L.dense_init(ks[1], (d, kv * dh), dt),
        "wv": L.dense_init(ks[2], (d, kv * dh), dt),
        "wo": L.dense_init(ks[3], (hq * dh, d), dt, fan_in=hq * dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dt)
        p["bk"] = jnp.zeros((kv * dh,), dt)
        p["bv"] = jnp.zeros((kv * dh,), dt)
    return p


@jax.named_scope("attn_proj")
def _proj_qkv(p, cfg, x, kv_x=None):
    """x: (B,S,d). Returns q (B,S,Hq,Dh), k/v (B,Skv,Hkv,Dh)."""
    b, s, _ = x.shape
    kv_x = x if kv_x is None else kv_x
    skv = kv_x.shape[1]
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", kv_x, p["wk"])
    v = jnp.einsum("bsd,de->bse", kv_x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head)
    k = k.reshape(b, skv, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, skv, cfg.n_kv_heads, cfg.d_head)
    return q, k, v


def _use_rope(cfg):
    return cfg.family in ("dense", "moe", "vlm", "hybrid")


def attn_full(p, cfg, x, *, positions, causal=True, window=None,
              attn_impl="chunked", kv_x=None, kv_positions=None):
    """Full-sequence attention. Returns (out (B,S,d), (k, v))."""
    q, k, v = _proj_qkv(p, cfg, x, kv_x)
    if cfg.bf16_grads and x.dtype == jnp.bfloat16:
        from repro.models.attention import bf16_grad
        q, k, v = bf16_grad(q), bf16_grad(k), bf16_grad(v)
    if _use_rope(cfg):
        q = L.apply_rope(q, positions, cfg.rope_theta)
        kp = positions if kv_positions is None else kv_positions
        k = L.apply_rope(k, kp, cfg.rope_theta)
    with jax.named_scope("attention"):
        o = attention(q, k, v, causal=causal, window=window,
                      q_offset=0, kv_offset=0, impl=attn_impl,
                      q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk)
    o = hints.row_input(o.reshape(x.shape[0], x.shape[1], -1))
    return _out_proj(p, o), (k, v)


def attn_decode(p, cfg, x, k_cache, v_cache, cache_len, *, window=None,
                block_tables=None):
    """Single-token attention. x: (B,1,d). ``cache_len`` is a scalar, or
    a per-row (B,) vector for fully-ragged continuous batching (each row
    rotates/masks at its own absolute position). With ``block_tables``
    (B, W), ``k_cache``/``v_cache`` are paged block pools (NB, bs, H,
    Dh) and each row's KV span is gathered through its table. Returns
    (out, k1, v1).
    """
    q, k1, v1 = _proj_qkv(p, cfg, x)
    if _use_rope(cfg):
        clen = jnp.asarray(cache_len, jnp.int32)
        pos = clen.reshape(-1, 1) if clen.ndim else \
            jnp.full((1,), clen, jnp.int32)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k1 = L.apply_rope(k1, pos, cfg.rope_theta)
    with jax.named_scope("attention"):
        o = decode_attention(q, k_cache, v_cache, cache_len, window=window,
                             extra_k=k1, extra_v=v1,
                             block_tables=block_tables)
    o = hints.row_input(o.reshape(x.shape[0], 1, -1))
    return _out_proj(p, o), k1, v1


def attn_chunk(p, cfg, x, k_hist, v_hist, hist_len, *, positions,
               attn_impl="chunked"):
    """Chunked-prefill attention: x (B,S,d) is one prompt chunk whose
    first token sits at absolute position ``hist_len``; ``k_hist``/
    ``v_hist`` (B,C,Hkv,Dh) are the slot's cached rows (valid to
    ``hist_len``). Returns (out (B,S,d), (k, v)) — the chunk's own KV,
    for the caller to splice at offset ``hist_len``."""
    q, k, v = _proj_qkv(p, cfg, x)
    if _use_rope(cfg):
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    impl = "pallas" if attn_impl == "pallas" else "chunked"
    with jax.named_scope("attention"):
        o = prefill_over_cache(q, k_hist, v_hist, hist_len, k, v, impl=impl)
    o = hints.row_input(o.reshape(x.shape[0], x.shape[1], -1))
    return _out_proj(p, o), (k, v)


@jax.named_scope("attn_proj")
def _out_proj(p, o):
    return jnp.einsum("bse,ed->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# transformer decoder layers (dense / moe / vlm + whisper enc/dec)
# ---------------------------------------------------------------------------

def init_decoder_layer(key, cfg, ffn_kind="dense", d_ff=None, cross=False):
    ks = jax.random.split(key, 5)
    p = {
        "ln1": L.init_norm(ks[0], cfg),
        "attn": init_attn(ks[1], cfg),
        "ln2": L.init_norm(ks[2], cfg),
    }
    if ffn_kind == "moe":
        p["moe"] = M.init_moe(ks[3], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[3], cfg, d_ff=d_ff)
    if cross:
        p["ln_x"] = L.init_norm(ks[4], cfg)
        p["xattn"] = init_attn(ks[4], cfg)
    return p


@jax.named_scope("mlp")
def _apply_ffn(p, cfg, x):
    if "moe" in p:
        return M.apply_moe(p["moe"], cfg, x)
    return L.apply_mlp(p["mlp"], cfg, x)


def decoder_block(p, cfg, x, *, positions, attn_impl, causal=True,
                  window=None, enc_out=None):
    h = L.apply_norm(p["ln1"], cfg, x)
    a, (k, v) = attn_full(p["attn"], cfg, h, positions=positions,
                          causal=causal, window=window, attn_impl=attn_impl)
    # §Perf C6: pin the residual stream at every add, not just the block
    # boundary, so sequence-parallel layouts survive through the block.
    x = hints.hidden(x + a, cfg.act_shard)
    if enc_out is not None:  # cross-attention (whisper decoder)
        h = L.apply_norm(p["ln_x"], cfg, x)
        a, (xk, xv) = attn_full(
            p["xattn"], cfg, h, positions=positions, causal=False,
            attn_impl=attn_impl, kv_x=enc_out,
            kv_positions=jnp.arange(enc_out.shape[1]))
        x = x + a
    else:
        xk = xv = None
    h = L.apply_norm(p["ln2"], cfg, x)
    x = x + _apply_ffn(p, cfg, h)
    return hints.hidden(x, cfg.act_shard), (k, v, xk, xv)


def decoder_block_chunk(p, cfg, x, k_hist, v_hist, hist_len, *, positions,
                        attn_impl="chunked"):
    """Decoder block over one prompt chunk with a nonzero KV history.
    Attention-family FFN (dense mlp or moe) — the chunked-prefill
    analogue of :func:`decoder_block` / :func:`decoder_block_decode`."""
    h = L.apply_norm(p["ln1"], cfg, x)
    a, (k, v) = attn_chunk(p["attn"], cfg, h, k_hist, v_hist, hist_len,
                           positions=positions, attn_impl=attn_impl)
    x = hints.hidden(x + a, cfg.act_shard)
    h = L.apply_norm(p["ln2"], cfg, x)
    x = x + _apply_ffn(p, cfg, h)
    return hints.hidden(x, cfg.act_shard), (k, v)


def decoder_block_decode(p, cfg, x, k_cache, v_cache, cache_len, *,
                         window=None, cross_k=None, cross_v=None,
                         block_tables=None):
    h = L.apply_norm(p["ln1"], cfg, x)
    a, k1, v1 = attn_decode(p["attn"], cfg, h, k_cache, v_cache,
                            cache_len, window=window,
                            block_tables=block_tables)
    # pin the residual stream like the other blocks do — without it the
    # model-sharded wo/w_down outputs leave d_model sharded and the next
    # rmsnorm becomes a cross-device reduce (order-dependent, breaks the
    # serving bitwise gate)
    x = hints.hidden(x + a, cfg.act_shard)
    if cross_k is not None:
        h = L.apply_norm(p["ln_x"], cfg, x)
        q, _, _ = _proj_qkv(p["xattn"], cfg, h)
        with jax.named_scope("attention"):
            o = decode_attention(q, cross_k, cross_v,
                                 cross_k.shape[1])  # all slots valid
        o = hints.row_input(o.reshape(x.shape[0], 1, -1))
        x = x + _out_proj(p["xattn"], o)
    h = L.apply_norm(p["ln2"], cfg, x)
    x = hints.hidden(x + _apply_ffn(p, cfg, h), cfg.act_shard)
    return x, k1, v1


# ---------------------------------------------------------------------------
# init_params — family dispatch
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames="cfg")
def init_params(key, cfg):
    """Random parameters for ``cfg``, drawn from ``key``. Jitted, so
    each leaf is drawn in float32 and cast inside one program: only the
    ``cfg.dtype`` weights are ever materialised on the device (an eager
    init holds a float32 transient of the largest stacked leaf)."""
    ks = jax.random.split(key, 8)
    p = {"embed": L.init_embedding(ks[0], cfg),
         "final_norm": L.init_norm(ks[1], cfg)}
    if not cfg.tie_embeddings:
        p["head"] = L.embed_init(ks[2], (cfg.vocab_size, cfg.d_model),
                                 L.dtype_of(cfg))

    fam = cfg.family
    if fam in TRANSFORMER_FAMILIES:
        n_first = cfg.first_dense_layers if cfg.is_moe else 0
        kind = "moe" if cfg.is_moe else "dense"
        if n_first:
            p["first_layers"] = [
                init_decoder_layer(k, cfg, "dense",
                                   d_ff=cfg.d_ff_first_dense or cfg.d_ff)
                for k in jax.random.split(ks[3], n_first)
            ]
        p["layers"] = _stack_init(
            lambda k: init_decoder_layer(k, cfg, kind),
            ks[4], cfg.n_layers - n_first)
    elif fam == "audio":
        p["enc_layers"] = _stack_init(
            lambda k: init_decoder_layer(k, cfg, "dense"),
            ks[3], cfg.n_encoder_layers)
        p["enc_norm"] = L.init_norm(ks[5], cfg)
        p["layers"] = _stack_init(
            lambda k: init_decoder_layer(k, cfg, "dense", cross=True),
            ks[4], cfg.n_layers)
    elif fam == "ssm":  # xLSTM
        every = cfg.slstm_every or (cfg.n_layers + 1)
        n_super = max(1, cfg.n_layers // every)
        n_m_inner = every - 1 if cfg.slstm_every else cfg.n_layers
        p["mlstm"] = _stack_init(
            lambda k: _stack_init(lambda k2: X.init_mlstm(k2, cfg), k,
                                  n_m_inner),
            ks[3], n_super)
        if cfg.slstm_every:
            p["slstm"] = _stack_init(lambda k: X.init_slstm(k, cfg),
                                     ks[4], n_super)
    elif fam == "hybrid":  # Zamba2
        every = cfg.attn_every
        n_groups = cfg.n_layers // every
        def init_mamba_layer(k):
            k1, k2 = jax.random.split(k)
            return {"ln": L.init_norm(k1, cfg),
                    "mamba": S.init_mamba2(k2, cfg)}
        p["mamba"] = _stack_init(
            lambda k: _stack_init(init_mamba_layer, k, every),
            ks[3], n_groups)
        p["shared"] = init_decoder_layer(ks[4], cfg, "dense")
    else:
        raise ValueError(f"unknown family {fam}")
    return p


# ---------------------------------------------------------------------------
# input embedding per family
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg, batch):
    """Returns (x (B,S,d), positions (S,), n_prefix) for the decoder."""
    x = L.embed_tokens(params["embed"], batch["tokens"])
    n_prefix = 0
    if cfg.family == "vlm" and "images" in batch:
        img = batch["images"].astype(x.dtype)  # (B, n_img, d) stub frontend
        x = jnp.concatenate([img, x], axis=1)
        n_prefix = img.shape[1]
    s = x.shape[1]
    positions = jnp.arange(s)
    if cfg.family == "audio":
        x = x + L.sinusoidal_positions(positions, cfg.d_model)[None].astype(x.dtype)
    return hints.hidden(x, cfg.act_shard), positions, n_prefix


def _encode_audio(params, cfg, frames, attn_impl):
    """Whisper encoder over precomputed frame embeddings (B, T, d)."""
    pos = jnp.arange(frames.shape[1])
    x = frames + L.sinusoidal_positions(pos, cfg.d_model)[None].astype(frames.dtype)

    def body(h, lp):
        h, _ = decoder_block(lp, cfg, h, positions=pos, causal=False,
                             attn_impl=attn_impl)
        return h, None

    x, _ = jax.lax.scan(_remat(cfg, body), x, params["enc_layers"])
    return L.apply_norm(params["enc_norm"], cfg, x)


# ---------------------------------------------------------------------------
# forward (train / full-sequence path)
# ---------------------------------------------------------------------------

def forward(params, cfg, batch, *, attn_impl="chunked"):
    """Full-sequence logits (B, S, V) fp32 — the train/prefill path."""
    x, positions, _ = _embed_inputs(params, cfg, batch)
    fam = cfg.family

    if fam in TRANSFORMER_FAMILIES:
        for lp in params.get("first_layers", []):
            x, _ = decoder_block(lp, cfg, x, positions=positions,
                                 attn_impl=attn_impl,
                                 window=cfg.sliding_window)

        def body(h, lp):
            h, _ = decoder_block(lp, cfg, h, positions=positions,
                                 attn_impl=attn_impl,
                                 window=cfg.sliding_window)
            return h, None

        x, _ = jax.lax.scan(_remat(cfg, body), x, params["layers"])

    elif fam == "audio":
        enc = _encode_audio(params, cfg, batch["frames"], attn_impl)

        def body(h, lp):
            h, _ = decoder_block(lp, cfg, h, positions=positions,
                                 attn_impl=attn_impl, enc_out=enc)
            return h, None

        x, _ = jax.lax.scan(_remat(cfg, body), x, params["layers"])

    elif fam == "ssm":
        def super_body(h, lps):
            def m_body(hh, lp):
                hh, _ = X.apply_mlstm(lp, cfg, hh)
                return hh, None
            h, _ = jax.lax.scan(_remat(cfg, m_body), h, lps["m"])
            if "s" in lps:
                h, _ = X.apply_slstm(lps["s"], cfg, h)
            return h, None

        xs = {"m": params["mlstm"]}
        if "slstm" in params:
            xs["s"] = params["slstm"]
        x, _ = jax.lax.scan(super_body, x, xs)

    elif fam == "hybrid":
        shared = params["shared"]

        def group_body(h, lps):
            def m_body(hh, lp):
                y, _ = S.apply_mamba2(
                    lp["mamba"], cfg, L.apply_norm(lp["ln"], cfg, hh))
                return hh + y, None
            h, _ = jax.lax.scan(_remat(cfg, m_body), h, lps)
            h, _ = decoder_block(shared, cfg, h, positions=positions,
                                 attn_impl=attn_impl)
            return h, None

        x, _ = jax.lax.scan(group_body, x, params["mamba"])
    else:
        raise ValueError(fam)

    x = L.apply_norm(params["final_norm"], cfg, x)
    head = params["embed"]["table"] if cfg.tie_embeddings else params["head"]
    return hints.logits(L.logits_from_hidden(head, x))


def loss_fn(params, cfg, batch, *, attn_impl="chunked"):
    """Next-token cross-entropy. Labels (B, S_tokens) aligned to tokens;
    vlm image-prefix positions carry no loss."""
    logits = forward(params, cfg, batch, attn_impl=attn_impl)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:  # vlm prefix
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    mask = batch.get("loss_mask")
    loss = L.softmax_cross_entropy(logits, labels, mask)
    return loss, {"loss": loss}


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _kv_capacity(cfg, capacity):
    if cfg.sliding_window is not None:
        return min(capacity, cfg.sliding_window)
    return capacity


def cache_struct(cfg, batch_size, capacity, dtype=None):
    """Shape/dtype tree of the decode cache (used for zeros + specs)."""
    dt = dtype or L.dtype_of(cfg)
    fam = cfg.family
    kvd = cfg.n_kv_heads * 0 + cfg.d_head  # readability
    out = {"len": ((), jnp.int32)}
    if fam in TRANSFORMER_FAMILIES:
        c = _kv_capacity(cfg, capacity)
        kshape = (cfg.n_layers, batch_size, c, cfg.n_kv_heads, cfg.d_head)
        out["k"] = (kshape, dt)
        out["v"] = (kshape, dt)
    elif fam == "audio":
        kshape = (cfg.n_layers, batch_size, capacity, cfg.n_kv_heads,
                  cfg.d_head)
        xshape = (cfg.n_layers, batch_size, cfg.encoder_len,
                  cfg.n_kv_heads, cfg.d_head)
        out["k"] = (kshape, dt)
        out["v"] = (kshape, dt)
        out["cross_k"] = (xshape, dt)
        out["cross_v"] = (xshape, dt)
    elif fam == "ssm":
        every = cfg.slstm_every or (cfg.n_layers + 1)
        n_super = max(1, cfg.n_layers // every)
        n_m_inner = every - 1 if cfg.slstm_every else cfg.n_layers
        ms = X.mlstm_state_shape(cfg, batch_size)
        out["mlstm"] = ((n_super, n_m_inner) + ms, jnp.float32)
        if cfg.slstm_every:
            ss = X.slstm_state_shape(cfg, batch_size)
            for nm in ("slstm_c", "slstm_n", "slstm_h"):
                out[nm] = ((n_super,) + ss, jnp.float32)
    elif fam == "hybrid":
        every = cfg.attn_every
        n_groups = cfg.n_layers // every
        d_in = cfg.ssm_expand * cfg.d_model
        h = d_in // cfg.ssm_head_dim
        conv_c = d_in + 2 * cfg.ssm_state
        out["ssm"] = ((n_groups, every, batch_size, h, cfg.ssm_head_dim,
                       cfg.ssm_state), jnp.float32)
        out["conv"] = ((n_groups, every, batch_size, cfg.ssm_conv - 1,
                        conv_c), dt)
        kshape = (n_groups, batch_size, capacity, cfg.n_kv_heads, cfg.d_head)
        out["k"] = (kshape, dt)
        out["v"] = (kshape, dt)
    return out


def init_cache(cfg, batch_size, capacity):
    return {k: jnp.zeros(sh, dt)
            for k, (sh, dt) in cache_struct(cfg, batch_size, capacity).items()}


def cache_batch_axes(cache: dict) -> dict:
    """Batch-dim index per cache leaf (None = no batch dim). The single
    source of truth for per-leaf batch axes — the serving engine's slot
    splice and ``decode_step``'s live-mask merges both derive from it."""
    axes = {}
    for name, leaf in cache.items():
        if name == "len" or getattr(leaf, "ndim", 0) == 0:
            axes[name] = None
        elif name in ("k", "v", "cross_k", "cross_v"):
            axes[name] = 1        # (L|G, B, C, H, Dh)
        elif name in ("ssm", "conv", "mlstm"):
            axes[name] = 2        # (outer, inner, B, ...)
        elif name.startswith("slstm"):
            axes[name] = 1        # (outer, B, ...)
        else:
            raise KeyError(f"unknown cache leaf {name}")
    return axes


def cache_spec(cfg, batch_size, capacity):
    return {k: jax.ShapeDtypeStruct(sh, dt)
            for k, (sh, dt) in cache_struct(cfg, batch_size, capacity).items()}


# ---------------------------------------------------------------------------
# paged (block-table) cache — attention families only
# ---------------------------------------------------------------------------

def paged_pool_struct(cfg, num_blocks, block_size, dtype=None):
    """Shape/dtype of the shared paged KV pools: ``num_blocks`` blocks
    of ``block_size`` positions each, all layers stacked on the leading
    axis. Only attention families (dense/moe/vlm) page their KV;
    recurrent state is O(1)/slot and stays contiguous."""
    if cfg.family not in TRANSFORMER_FAMILIES:
        raise ValueError(f"paged KV pools unsupported for {cfg.family!r}")
    dt = dtype or L.dtype_of(cfg)
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads,
             cfg.d_head)
    return {"k": (shape, dt), "v": (shape, dt)}


def init_paged_pools(cfg, num_blocks, block_size):
    st = paged_pool_struct(cfg, num_blocks, block_size)
    return (jnp.zeros(*st["k"]), jnp.zeros(*st["v"]))


def paged_cache_spec(cfg, batch_size, capacity, block_size,
                     num_blocks=None, *, ragged=False):
    """ShapeDtypeStruct pytree of a paged decode cache (tracing /
    simulator): pools + per-row block tables wide enough for
    ``capacity`` positions. ``num_blocks`` defaults to exactly the
    resident worst case, ``batch * ceil(capacity/block_size)``."""
    w = -(-capacity // block_size)
    nb = num_blocks or batch_size * w
    st = paged_pool_struct(cfg, nb, block_size)
    out = {k: jax.ShapeDtypeStruct(sh, dt) for k, (sh, dt) in st.items()}
    out["block_tab"] = jax.ShapeDtypeStruct((batch_size, w), jnp.int32)
    out["len"] = jax.ShapeDtypeStruct((batch_size,) if ragged else (),
                                      jnp.int32)
    return out


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

@jax.named_scope("kv_write")
def _write_kv(cache_arr, kv, start):
    """cache_arr (L,B,C,H,Dh) <- kv (L,B,S,H,Dh) at slot ``start``."""
    return jax.lax.dynamic_update_slice(
        cache_arr, kv.astype(cache_arr.dtype), (0, 0, start, 0, 0))


def prefill(params, cfg, batch, capacity, *, attn_impl="chunked",
            logit_index=None, length=None):
    """Process the prompt, fill the cache. Returns (last logits (B,V),
    cache).

    ``logit_index`` (scalar or (B,) int32): position to read logits
    from instead of the last one — used for right-padded (bucketed)
    prompts where the true last token sits at ``n_prompt - 1``. Causal
    attention guarantees pad positions never influence earlier rows;
    their garbage KV is masked at decode by per-row cache lengths.

    ``length`` (traced scalar int32): the prompt's true length when the
    batch is right-padded and the family is recurrent (ssm/hybrid) —
    recurrent state would otherwise advance through the pads. The scan
    is length-masked (pad steps get decay 1 and zero input, the same
    values the SSD engine's internal chunk padding uses), so the final
    state — and hence every decoded token — is bitwise that of the
    exact-length prompt. Ignored for attention families, whose causal
    mask already makes right-padding harmless.
    """
    x, positions, _ = _embed_inputs(params, cfg, batch)
    s = x.shape[1]
    b = x.shape[0]
    cache = init_cache(cfg, b, capacity)
    fam = cfg.family

    if fam in TRANSFORMER_FAMILIES:
        kvs = []
        for lp in params.get("first_layers", []):
            x, (k, v, _, _) = decoder_block(
                lp, cfg, x, positions=positions, attn_impl=attn_impl,
                window=cfg.sliding_window)
            kvs.append((k, v))

        def body(h, lp):
            h, (k, v, _, _) = decoder_block(
                lp, cfg, h, positions=positions, attn_impl=attn_impl,
                window=cfg.sliding_window)
            return h, (k, v)

        x, (ks, vs) = jax.lax.scan(_remat(cfg, body), x, params["layers"])
        if kvs:
            k0 = jnp.stack([k for k, _ in kvs])
            v0 = jnp.stack([v for _, v in kvs])
            ks = jnp.concatenate([k0, ks], axis=0)
            vs = jnp.concatenate([v0, vs], axis=0)
        c = cache["k"].shape[2]
        if s >= c:
            # Rolling (SWA) cache: slot invariant is pos % c, so place the
            # window tail (tokens s-c .. s-1) rotated by s % c.
            ks, vs = ks[:, :, s - c:], vs[:, :, s - c:]
            shift = s % c
            with jax.named_scope("kv_write"):
                if shift:
                    ks = jnp.roll(ks, shift, axis=2)
                    vs = jnp.roll(vs, shift, axis=2)
                cache["k"], cache["v"] = (ks.astype(cache["k"].dtype),
                                          vs.astype(cache["v"].dtype))
        else:
            cache["k"] = _write_kv(cache["k"], ks, 0)
            cache["v"] = _write_kv(cache["v"], vs, 0)

    elif fam == "audio":
        enc = _encode_audio(params, cfg, batch["frames"], attn_impl)

        def body(h, lp):
            h, (k, v, xk, xv) = decoder_block(
                lp, cfg, h, positions=positions, attn_impl=attn_impl,
                enc_out=enc)
            return h, (k, v, xk, xv)

        x, (ks, vs, xks, xvs) = jax.lax.scan(_remat(cfg, body), x,
                                             params["layers"])
        cache["k"] = _write_kv(cache["k"], ks, 0)
        cache["v"] = _write_kv(cache["v"], vs, 0)
        cache["cross_k"] = xks.astype(cache["cross_k"].dtype)
        cache["cross_v"] = xvs.astype(cache["cross_v"].dtype)

    elif fam == "ssm":
        lmask = (None if length is None
                 else jnp.arange(s) < jnp.asarray(length, jnp.int32))

        def super_body(h, lps):
            def m_body(hh, lp):
                hh, st = X.apply_mlstm(lp, cfg, hh, mask=lmask)
                return hh, st
            h, m_states = jax.lax.scan(_remat(cfg, m_body), h, lps["m"])
            s_state = None
            if "s" in lps:
                h, s_state = X.apply_slstm(lps["s"], cfg, h, mask=lmask)
            return h, (m_states, s_state)

        xs = {"m": params["mlstm"]}
        if "slstm" in params:
            xs["s"] = params["slstm"]
        x, (m_states, s_states) = jax.lax.scan(super_body, x, xs)
        cache["mlstm"] = m_states
        if s_states is not None:
            cache["slstm_c"], cache["slstm_n"], cache["slstm_h"] = s_states

    elif fam == "hybrid":
        shared = params["shared"]

        def group_body(h, lps):
            def m_body(hh, lp):
                y, (st, cv) = S.apply_mamba2(
                    lp["mamba"], cfg, L.apply_norm(lp["ln"], cfg, hh),
                    n_valid=length)
                return hh + y, (st, cv)
            h, (sts, cvs) = jax.lax.scan(_remat(cfg, m_body), h, lps)
            h, (k, v, _, _) = decoder_block(shared, cfg, h,
                                            positions=positions,
                                            attn_impl=attn_impl)
            return h, (sts, cvs, k, v)

        x, (sts, cvs, ks, vs) = jax.lax.scan(group_body, x, params["mamba"])
        cache["ssm"] = sts
        cache["conv"] = cvs.astype(cache["conv"].dtype)
        cache["k"] = _write_kv(cache["k"], ks, 0)
        cache["v"] = _write_kv(cache["v"], vs, 0)

    else:
        raise ValueError(fam)

    cache["len"] = jnp.asarray(s, jnp.int32)
    if logit_index is None:
        x = x[:, -1:]
    else:
        idx = jnp.asarray(logit_index, jnp.int32).reshape(-1, 1, 1)
        x = jnp.take_along_axis(x, idx, axis=1)
    x = L.apply_norm(params["final_norm"], cfg, x)
    head = params["embed"]["table"] if cfg.tie_embeddings else params["head"]
    return L.logits_from_hidden(head, x)[:, 0], cache


def prefill_chunk(params, cfg, batch, k_hist, v_hist, hist_len, *,
                  attn_impl="chunked", logit_index=None, block_table=None):
    """Process one prompt chunk against cached history (chunked /
    Sarathi-style prefill). Attention families only (dense/moe/vlm, no
    rolling SWA) — recurrent state cannot resume from a KV view, those
    families fall back to blocking prefill at the scheduler.

    batch: ``{"tokens": (B, S)}`` — the chunk, right-padded to a static
    length; the first chunk of a vlm prompt also carries ``"images"``
    (the image-token prefix occupies positions ``0..n_img-1``).
    ``k_hist``/``v_hist`` (L, B, C, Hkv, Dh): dense per-layer views of
    the slot's cache (contiguous rows, or a block-table gather of a
    paged pool), valid to ``hist_len`` (traced scalar) — chunk *k*
    attends chunks ``0..k-1`` through them. Pad-position KV is garbage
    downstream code masks by length, exactly like bucketed prefill.
    With ``block_table`` (W,), ``k_hist``/``v_hist`` are the paged pools
    (L, NB, bs, Hkv, Dh) and each layer gathers the slot's blocks inside
    the layer scan, so only one layer's history view is ever live.

    Returns (logits (B, V) read at ``logit_index`` within the chunk,
    ks, vs (L, B, S, Hkv, Dh)) — the chunk's KV rows, to be spliced at
    offset ``hist_len``.
    """
    if cfg.family not in TRANSFORMER_FAMILIES:
        raise ValueError(f"chunked prefill unsupported for family "
                         f"{cfg.family!r}")
    if cfg.sliding_window is not None:
        raise ValueError("chunked prefill does not support rolling SWA "
                         "caches")
    x, _, _ = _embed_inputs(params, cfg, batch)
    s = x.shape[1]
    positions = jnp.asarray(hist_len, jnp.int32) + jnp.arange(s)
    n_first = len(params.get("first_layers", []))

    @jax.named_scope("attention")
    def view(layer_hist):
        if block_table is None:
            return layer_hist
        return gather_kv_blocks(layer_hist, block_table[None])

    k_news, v_news = [], []
    for i, lp in enumerate(params.get("first_layers", [])):
        x, (k1, v1) = decoder_block_chunk(
            lp, cfg, x, view(k_hist[i]), view(v_hist[i]), hist_len,
            positions=positions, attn_impl=attn_impl)
        k_news.append(k1)
        v_news.append(v1)

    def body(h, xs):
        lp, kh, vh = xs
        h, (k1, v1) = decoder_block_chunk(lp, cfg, h, view(kh), view(vh),
                                          hist_len, positions=positions,
                                          attn_impl=attn_impl)
        return h, (k1, v1)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], k_hist[n_first:], v_hist[n_first:]))
    if k_news:
        ks = jnp.concatenate([jnp.stack(k_news), ks], axis=0)
        vs = jnp.concatenate([jnp.stack(v_news), vs], axis=0)

    if logit_index is None:
        x = x[:, -1:]
    else:
        idx = jnp.asarray(logit_index, jnp.int32).reshape(-1, 1, 1)
        x = jnp.take_along_axis(x, idx, axis=1)
    x = L.apply_norm(params["final_norm"], cfg, x)
    head = params["embed"]["table"] if cfg.tie_embeddings else params["head"]
    return L.logits_from_hidden(head, x)[:, 0], ks, vs


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

@jax.named_scope("kv_write")
def write_window(cache_arr, kv, row, pos, n_valid):
    """Write one row's S tokens of KV ``kv`` (L|G, 1, S, H, Dh) into row
    ``row`` of ``cache_arr`` (L|G, B, C, H, Dh) at positions ``pos ..
    pos+S-1``; tokens at index ``>= n_valid`` and positions past the
    capacity keep the cache's previous contents exactly.

    A read-modify-write ``dynamic_update_slice`` of the S-position
    window, never a scatter over (row, position): the TPU keeps the
    cache with its position axis minor, and a scatter there makes the
    compiler copy the whole cache into a row-major temporary. The
    update aliases a donated cache buffer in place."""
    kv = kv.astype(cache_arr.dtype)
    s, c = kv.shape[2], cache_arr.shape[2]
    start = jnp.clip(pos, 0, c - s)        # the window a DUS can land
    idx = (0, row, start, 0, 0)
    shift = pos - start                    # > 0 only past the capacity
    # token t sits at window slot t + shift: shift the tokens in with a
    # dynamic slice of the zero-padded window (a gather here would pull
    # the whole cache into a row-major layout, like a scatter)
    new = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([jnp.zeros_like(kv), kv], axis=2), s - shift, s,
        axis=2)
    t = jnp.arange(s) - shift              # token index at each slot
    keep = (t >= 0) & (t < n_valid)
    old = jax.lax.dynamic_slice(cache_arr, idx, kv.shape)
    return jax.lax.dynamic_update_slice(
        cache_arr, jnp.where(keep[None, None, :, None, None], new, old), idx)


@jax.named_scope("kv_write")
def _write_tokens_kv(cache_arr, kv, pos, live=None):
    """Write S new tokens' KV ``kv`` (L|G, B, S, H, Dh) into
    ``cache_arr`` (L|G, B, C, H, Dh) at per-row positions ``pos[b] ..
    pos[b]+S-1`` (``pos`` a scalar or a (B,) vector). Rows where
    ``live`` is False, and positions past the capacity, keep their
    previous contents (rejected verify candidates and free slots are
    masked downstream by the per-row length vector). One
    :func:`write_window` per row."""
    b, s = cache_arr.shape[1], kv.shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    for i in range(b):
        n_valid = s if live is None else jnp.where(live[i], s, 0)
        cache_arr = write_window(cache_arr, kv[:, i:i + 1], i, pos[i],
                                 n_valid)
    return cache_arr


@jax.named_scope("kv_write")
def write_pool_positions(pool, kv, blk, off):
    """Write tokens' KV ``kv`` (L, R, S, H, Dh) into the shared block
    pool (L, NB, bs, H, Dh): token (r, t) lands in block ``blk[r, t]``
    at row ``off[r, t]``; entries with ``blk >= NB`` (the sentinel)
    leave the pool unchanged. A loop of read-modify-write updates, one
    per token: a scatter would make the compiler copy the pool. The
    decode and verify writes (a token or a few in each of many rows)
    come here; a prefill chunk goes to :func:`write_pool_chunk`.

    Where Dh is not a multiple of the TPU's 128 lanes (phi3's 96) the
    compiler makes the block axis the lane axis of the pool's tiles
    (``{1,4,3,2,0:T(8,128)}``), so each token's update rewrites one lane
    of every tile of its 128-block group."""
    nb = pool.shape[1]
    r, s = kv.shape[1], kv.shape[2]
    kv = kv.astype(pool.dtype)
    upd = (pool.shape[0], 1, 1) + pool.shape[3:]

    def body(n, pool):
        i, t = n // s, n % s
        b = blk[i, t]
        new = jax.lax.dynamic_slice(kv, (0, i, t, 0, 0), upd)
        idx = (0, jnp.minimum(b, nb - 1), off[i, t], 0, 0)
        old = jax.lax.dynamic_slice(pool, idx, upd)
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(b < nb, new, old), idx)

    return jax.lax.fori_loop(0, r * s, body, pool)


POOL_LANES = 128    # the TPU's lanes: blocks in one lane group of a pool


def lane_groups(num_blocks):
    """``(GW, starts)``: a pool's blocks in groups of ``GW = 128`` (all of
    a smaller pool), group ``g`` the GW blocks from ``starts[g]``, the
    last group pulled back to end at the pool's end. Block ``b`` belongs
    to group ``b // GW``."""
    gw = min(POOL_LANES, num_blocks)
    return gw, [min(g * gw, num_blocks - gw)
                for g in range(-(-num_blocks // gw))]


@jax.named_scope("kv_write")
def write_pool_chunk(pool, kv, lanes, shift, n_valid):
    """Write one row's prefill chunk ``kv`` (L, 1, S, H, Dh) into the
    block pool (L, NB, bs, H, Dh), one lane group of blocks at a time.

    Token ``t`` sits at chunk position ``shift + t``: chunk block
    ``(shift + t) // bs``, row ``(shift + t) % bs``. ``lanes[g, j]`` is
    the chunk block that lands in block ``j`` of lane group ``g``
    (:func:`lane_groups`), or -1 where that block keeps its contents;
    a group with no chunk block is not touched. Tokens ``t >= n_valid``
    (a pad tail) and the rows of a chunk block before ``shift`` or past
    the last token keep theirs too.

    On the TPU the pool's block axis is the lane axis of its tiles
    (:func:`write_pool_positions`), so a group of 128 blocks of one
    layer is a whole number of tiles: it is read once, merged with every
    token of the chunk that falls in it in one fused select, and written
    back with one ``dynamic_update_slice`` at a start fixed at compile
    time (on a v5e, a start the compiler could not prove a multiple of
    128 made each layer's write four to five times slower), where a
    per-token loop rewrites the same tiles once per token. One layer at a time keeps the merged group small enough for
    the compiler to hold on chip (phi3: 12.6 MB). The chunk's blocks
    move into their lanes by a one-hot matmul over the bytes of the
    values (a gather along the lane axis makes the compiler relayout the
    group): every product is a byte times 0, 1 or 256 and at most two
    terms of a sum are nonzero, so each 16-bit word comes out exact,
    whatever its bits."""
    n_layers, _, s, h, dh = kv.shape
    bs = pool.shape[2]
    gw, starts = lane_groups(pool.shape[1])
    nq = -(-(s + bs - 1) // bs)            # blocks a chunk can touch
    kv = kv.astype(pool.dtype)
    zeros = partial(jnp.zeros, dtype=kv.dtype)
    rows = jnp.concatenate([zeros((n_layers, bs, h, dh)), kv[:, 0],
                            zeros((n_layers, nq * bs - s, h, dh))], axis=1)
    rows = jax.lax.dynamic_slice_in_dim(rows, bs - shift, nq * bs, axis=1)
    # (L, bs*H*Dh*w, 2*nq): each 16-bit word's high and low byte for
    # every chunk block, the blocks minor, so one layer is the left
    # operand of a plain (rows x 2*nq) @ (2*nq x GW) matmul
    words = jax.lax.bitcast_convert_type(rows, jnp.uint16).reshape(
        n_layers, nq, -1).transpose(0, 2, 1)
    planes = jnp.concatenate([words >> 8, words & 0xFF], axis=-1).astype(
        jnp.bfloat16)

    def merge(lane_q, start):
        hit = (lane_q[None, :] == jnp.arange(nq)[:, None]).astype(
            jnp.bfloat16)
        hit = jnp.concatenate([hit * 256, hit])
        t = lane_q[:, None] * bs + jnp.arange(bs)[None, :] - shift
        keep = (lane_q[:, None] >= 0) & (t >= 0) & (t < n_valid)

        def layer(i, pool):
            moved = jnp.dot(planes[i], hit,
                            preferred_element_type=jnp.float32)
            out = jnp.moveaxis(moved.astype(jnp.uint16).reshape(
                bs, h, dh, -1, gw), -1, 0)
            if pool.dtype.itemsize == 2:
                out = out[..., 0]
            new = jax.lax.bitcast_convert_type(out, pool.dtype)[None]
            idx = (i, start, 0, 0, 0)
            old = jax.lax.dynamic_slice(pool, idx, new.shape)
            return jax.lax.dynamic_update_slice(
                pool, jnp.where(keep[None, :, :, None, None], new, old),
                idx)
        return lambda pool: jax.lax.fori_loop(0, n_layers, layer, pool)

    for g, start in enumerate(starts):
        pool = jax.lax.cond(jnp.any(lanes[g] >= 0),
                            merge(lanes[g], start), lambda p: p, pool)
    return pool


@jax.named_scope("kv_write")
def write_pool_blocks(pool, rows, blocks):
    """Dense rows (L, 1, n*bs, H, Dh) -> pool blocks ``blocks`` (n,):
    block i of the rows lands in pool block ``blocks[i]``; sentinel
    entries (>= NB) are dropped. A loop of whole-block updates: a
    scatter along the block axis, the lane axis of the pool's tiles
    where Dh is not a multiple of 128 (:func:`write_pool_positions`),
    makes the compiler copy the pool."""
    nb, bs = pool.shape[1], pool.shape[2]
    rows = rows.astype(pool.dtype)
    upd = (pool.shape[0], 1, bs) + pool.shape[3:]

    def body(i, pool):
        b = blocks[i]
        new = jax.lax.dynamic_slice_in_dim(rows, i * bs, bs, axis=2)
        idx = (0, jnp.minimum(b, nb - 1), 0, 0, 0)
        old = jax.lax.dynamic_slice(pool, idx, upd)
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(b < nb, new, old), idx)

    return jax.lax.fori_loop(0, blocks.shape[0], body, pool)


@jax.named_scope("kv_write")
def _write_tokens_kv_paged(pool, kv, block_tab, pos, live=None):
    """Paged analogue of :func:`_write_tokens_kv`: S tokens' KV ``kv``
    (L, B, S, H, Dh) land in the shared block pool (L, NB, bs, H, Dh) at
    each row's ``pos[b] + 0..S-1`` via its block table (B, W). Rows that
    are not live, positions past the table's capacity, and sentinel
    (never-allocated) table entries all keep the pool unchanged — a
    verify window is only backed by real blocks up to the row's commit
    cap, everything beyond is rejected-candidate garbage."""
    nb, bs = pool.shape[1], pool.shape[2]
    b, s = kv.shape[1], kv.shape[2]
    w = block_tab.shape[1]
    pos2 = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
            [:, None] + jnp.arange(s)[None, :])              # (B, S)
    blk = jnp.take_along_axis(block_tab, jnp.minimum(pos2 // bs, w - 1),
                              axis=1)                        # (B, S)
    drop = pos2 >= w * bs
    if live is not None:
        drop = drop | ~live.reshape(-1, 1)
    return write_pool_positions(pool, kv, jnp.where(drop, nb, blk),
                                pos2 % bs)


def _merge_rows(new, old, live, axis):
    """Per-row live-mask merge for O(1) recurrent state leaves: rows
    where ``live`` is False keep their previous state."""
    if live is None:
        return new
    shape = [1] * new.ndim
    shape[axis] = -1
    return jnp.where(live.reshape(shape), new, old)


def decode_step(params, cfg, tokens, cache, *, live=None):
    """tokens: (B, 1) int32. Returns (logits (B, V) fp32, new cache).

    ``cache['len']`` may be a scalar (all rows at the same position —
    the straight-line generation path) or a per-row (B,) vector (fully
    ragged continuous batching: every serving slot advances at its own
    absolute position in one dispatch). ``live`` ((B,) bool, optional)
    freezes non-live rows: their KV rows, recurrent state, and length
    are left exactly as they were, so a serving engine can run free /
    retired slots through the same jitted step with no post-hoc cache
    merge.

    Paged caches: when ``cache`` carries a ``block_tab`` leaf (B, W)
    its ``k``/``v`` leaves are shared block pools (L, NB, bs, H, Dh)
    — each attention layer gathers per-row KV through the block table
    and the new token's KV is scattered to block ``tab[b, pos//bs]``,
    offset ``pos % bs``. Attention families only."""
    x = L.embed_tokens(params["embed"], tokens)
    n = jnp.asarray(cache["len"], jnp.int32)
    fam = cfg.family
    btab = cache.get("block_tab")
    if btab is not None and (fam not in TRANSFORMER_FAMILIES
                             or cfg.sliding_window is not None):
        raise ValueError("paged cache requires an attention family "
                         "without a rolling SWA cache")

    if fam in TRANSFORMER_FAMILIES:
        if cfg.sliding_window is not None:
            slot = n % cache["k"].shape[2]
        else:
            slot = n
        n_first = len(params.get("first_layers", []))
        k_news, v_news = [], []
        for i, lp in enumerate(params.get("first_layers", [])):
            x, k1, v1 = decoder_block_decode(
                lp, cfg, x, cache["k"][i], cache["v"][i], n,
                window=cfg.sliding_window, block_tables=btab)
            k_news.append(k1)
            v_news.append(v1)

        def body(h, xs):
            lp, kc, vc = xs
            h, k1, v1 = decoder_block_decode(lp, cfg, h, kc, vc, n,
                                             window=cfg.sliding_window,
                                             block_tables=btab)
            return h, (k1, v1)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["k"][n_first:],
                      cache["v"][n_first:]))
        if k_news:
            ks = jnp.concatenate([jnp.stack(k_news), ks], axis=0)
            vs = jnp.concatenate([jnp.stack(v_news), vs], axis=0)
        if btab is None:
            cache["k"] = _write_tokens_kv(cache["k"], ks, slot, live)
            cache["v"] = _write_tokens_kv(cache["v"], vs, slot, live)
        else:
            cache["k"] = _write_tokens_kv_paged(cache["k"], ks, btab, n,
                                                live)
            cache["v"] = _write_tokens_kv_paged(cache["v"], vs, btab, n,
                                                live)

    elif fam == "audio":
        pos = n.reshape(-1, 1) if n.ndim else jnp.full((1, 1), n, jnp.int32)
        x = x + L.sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)

        def body(h, xs):
            lp, kc, vc, xk, xv = xs
            h, k1, v1 = decoder_block_decode(lp, cfg, h, kc, vc, n,
                                             cross_k=xk, cross_v=xv)
            return h, (k1, v1)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        cache["k"] = _write_tokens_kv(cache["k"], ks, n, live)
        cache["v"] = _write_tokens_kv(cache["v"], vs, n, live)

    elif fam == "ssm":
        def super_body(h, xs):
            def m_body(hh, mxs):
                lp, st = mxs
                hh, st = X.mlstm_decode_step(lp, cfg, hh, st)
                return hh, st
            h, m_states = jax.lax.scan(m_body, h, (xs["m"], xs["mst"]))
            out = {"mst": m_states}
            if "s" in xs:
                sst = (xs["sc"], xs["sn"], xs["sh"])
                h, sst = X.slstm_decode_step(xs["s"], cfg, h, sst)
                out.update(sc=sst[0], sn=sst[1], sh=sst[2])
            return h, out

        xs = {"m": params["mlstm"], "mst": cache["mlstm"]}
        if "slstm" in params:
            xs.update(s=params["slstm"], sc=cache["slstm_c"],
                      sn=cache["slstm_n"], sh=cache["slstm_h"])
        x, outs = jax.lax.scan(super_body, x, xs)
        axes = cache_batch_axes(cache)
        cache["mlstm"] = _merge_rows(outs["mst"], cache["mlstm"], live,
                                     axes["mlstm"])
        if "slstm" in params:
            for nm, new in (("slstm_c", outs["sc"]), ("slstm_n", outs["sn"]),
                            ("slstm_h", outs["sh"])):
                cache[nm] = _merge_rows(new, cache[nm], live, axes[nm])

    elif fam == "hybrid":
        shared = params["shared"]

        def group_body(h, xs):
            def m_body(hh, mxs):
                lp, st, cv = mxs
                y, (st, cv) = S.mamba2_decode_step(
                    lp["mamba"], cfg, L.apply_norm(lp["ln"], cfg, hh), st, cv)
                return hh + y, (st, cv)
            h, (sts, cvs) = jax.lax.scan(
                m_body, h, (xs["lp"], xs["st"], xs["cv"]))
            h, k1, v1 = decoder_block_decode(shared, cfg, h, xs["k"],
                                             xs["v"], n)
            return h, {"st": sts, "cv": cvs, "k1": k1, "v1": v1}

        x, outs = jax.lax.scan(
            group_body, x,
            {"lp": params["mamba"], "st": cache["ssm"], "cv": cache["conv"],
             "k": cache["k"], "v": cache["v"]})
        axes = cache_batch_axes(cache)
        cache["ssm"] = _merge_rows(outs["st"], cache["ssm"], live,
                                   axes["ssm"])
        cache["conv"] = _merge_rows(outs["cv"].astype(cache["conv"].dtype),
                                    cache["conv"], live, axes["conv"])
        cache["k"] = _write_tokens_kv(cache["k"], outs["k1"], n, live)
        cache["v"] = _write_tokens_kv(cache["v"], outs["v1"], n, live)
    else:
        raise ValueError(fam)

    cache["len"] = n + 1 if live is None else n + live.astype(jnp.int32)
    x = L.apply_norm(params["final_norm"], cfg, x)
    head = params["embed"]["table"] if cfg.tie_embeddings else params["head"]
    return hints.logits(L.logits_from_hidden(head, x))[:, 0], cache


# ---------------------------------------------------------------------------
# speculative verify step
# ---------------------------------------------------------------------------

def verify_tokens(params, cfg, tokens, cache, *, live=None,
                  attn_impl="chunked"):
    """Verify ``S = gamma + 1`` candidate tokens per row in one dispatch
    (speculative decoding, LP-Spec direction).

    ``tokens`` (B, S) int32 is, per row, the pending token followed by
    the draft's ``gamma`` proposals; ``cache['len']`` is the per-row
    (B,) valid-history length (each serving slot verifies at its own
    absolute position — the fully-ragged batch). Every candidate
    attends the cached history (masked to the row's length) plus the
    causal prefix of the candidate window itself — the multi-token
    generalization of :func:`prefill_chunk`'s prefill-over-cache
    attention, evaluated at per-row offsets. Returns (logits (B, S, V)
    fp32 — position *i* holds the target's next-token distribution
    after consuming candidate *i* — and the new cache).

    All S candidate KVs are written at ``len .. len + S - 1`` (per-row,
    live-masked, positions past capacity dropped); rejection is cheap
    because rejected-position KV is exactly the garbage the per-row
    length vector already masks — the host simply keeps the row's
    length at the accepted prefix and the next dispatch overwrites.
    Paged caches additionally drop writes to never-allocated sentinel
    blocks, so the cache manager can bound allocation to each row's
    commit cap and free over-allocated blocks on rejection.

    ``gamma = 0`` (S = 1) degenerates to a single-token decode step —
    same masks, same write — verified against :func:`decode_step` in
    the test harness. Attention families only (no rolling SWA):
    recurrent state cannot roll back by masking."""
    if cfg.family not in TRANSFORMER_FAMILIES:
        raise ValueError(f"speculative verify unsupported for family "
                         f"{cfg.family!r}")
    if cfg.sliding_window is not None:
        raise ValueError("speculative verify does not support rolling "
                         "SWA caches (rollback cannot un-roll a window)")
    x = L.embed_tokens(params["embed"], tokens)             # (B, S, d)
    s = x.shape[1]
    n = jnp.asarray(cache["len"], jnp.int32).reshape(-1)    # (B,)
    positions = n[:, None] + jnp.arange(s)                  # (B, S)
    btab = cache.get("block_tab")

    @jax.named_scope("attention")
    def hist_view(kc, vc):
        if btab is None:
            return kc, vc
        return gather_kv_blocks(kc, btab), gather_kv_blocks(vc, btab)

    n_first = len(params.get("first_layers", []))
    k_news, v_news = [], []
    for i, lp in enumerate(params.get("first_layers", [])):
        kh, vh = hist_view(cache["k"][i], cache["v"][i])
        x, (k1, v1) = decoder_block_chunk(lp, cfg, x, kh, vh, n,
                                          positions=positions,
                                          attn_impl=attn_impl)
        k_news.append(k1)
        v_news.append(v1)

    def body(h, xs):
        lp, kc, vc = xs
        kh, vh = hist_view(kc, vc)
        h, (k1, v1) = decoder_block_chunk(lp, cfg, h, kh, vh, n,
                                          positions=positions,
                                          attn_impl=attn_impl)
        return h, (k1, v1)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], cache["k"][n_first:],
                  cache["v"][n_first:]))
    if k_news:
        ks = jnp.concatenate([jnp.stack(k_news), ks], axis=0)
        vs = jnp.concatenate([jnp.stack(v_news), vs], axis=0)
    if btab is None:
        cache["k"] = _write_tokens_kv(cache["k"], ks, n, live)
        cache["v"] = _write_tokens_kv(cache["v"], vs, n, live)
    else:
        cache["k"] = _write_tokens_kv_paged(cache["k"], ks, btab, n, live)
        cache["v"] = _write_tokens_kv_paged(cache["v"], vs, btab, n, live)
    cache["len"] = n + (s if live is None else s * live.astype(jnp.int32))
    x = L.apply_norm(params["final_norm"], cfg, x)
    head = params["embed"]["table"] if cfg.tie_embeddings else params["head"]
    return hints.logits(L.logits_from_hidden(head, x)), cache


def self_draft_params(params, cfg, n_draft_layers: int):
    """Self-draft fallback for speculative decoding: a draft model that
    reuses the target's embeddings, head, and **first k layers** — no
    second checkpoint needed, and the draft's early-exit hidden state is
    a decent proposal distribution for free (Medusa/early-exit
    folklore; LP-Spec's small-drafter direction). Returns
    ``(draft_params, draft_cfg)`` where every leaf aliases the target's
    arrays (no copy — the stacked layer leaves are sliced views).

    ``k`` is clamped to ``[1, n_layers]``; with ``k == n_layers`` the
    draft *is* the target (acceptance -> 100%, the high-acceptance
    workload the CI gate measures)."""
    if cfg.family not in TRANSFORMER_FAMILIES:
        raise ValueError(f"self-draft unsupported for family "
                         f"{cfg.family!r}")
    k = int(max(1, min(n_draft_layers, cfg.n_layers)))
    dp = {"embed": params["embed"], "final_norm": params["final_norm"]}
    if "head" in params:
        dp["head"] = params["head"]
    first = params.get("first_layers", [])
    n_first = len(first)
    if n_first:
        dp["first_layers"] = first[:min(k, n_first)]
    n_stack = max(0, k - n_first)
    dp["layers"] = jax.tree_util.tree_map(lambda x: x[:n_stack],
                                          params["layers"])
    dcfg = cfg.replace(
        n_layers=k,
        first_dense_layers=min(cfg.first_dense_layers, k)
        if cfg.is_moe else cfg.first_dense_layers)
    return dp, dcfg


# ---------------------------------------------------------------------------
# batch construction (concrete + specs)
# ---------------------------------------------------------------------------

def batch_struct(cfg, batch_size, seq_len, kind="train"):
    """Shape/dtype tree for a model input batch of the given kind."""
    dt = L.dtype_of(cfg)
    out = {}
    if kind == "decode":
        out["tokens"] = ((batch_size, 1), jnp.int32)
        return out
    s_tok = seq_len
    if cfg.family == "vlm" and cfg.n_image_tokens:
        s_tok = seq_len - cfg.n_image_tokens
        out["images"] = ((batch_size, cfg.n_image_tokens, cfg.d_model), dt)
    if cfg.family == "audio":
        out["frames"] = ((batch_size, cfg.encoder_len, cfg.d_model), dt)
    out["tokens"] = ((batch_size, s_tok), jnp.int32)
    if kind == "train":
        out["labels"] = ((batch_size, s_tok), jnp.int32)
    return out


def batch_spec(cfg, batch_size, seq_len, kind="train"):
    return {k: jax.ShapeDtypeStruct(sh, dt)
            for k, (sh, dt) in batch_struct(cfg, batch_size, seq_len,
                                            kind).items()}


def make_dummy_batch(key, cfg, batch_size, seq_len, kind="train"):
    out = {}
    for name, (sh, dt) in batch_struct(cfg, batch_size, seq_len,
                                       kind).items():
        key, sub = jax.random.split(key)
        if dt == jnp.int32:
            out[name] = jax.random.randint(sub, sh, 0, cfg.vocab_size,
                                           jnp.int32)
        else:
            out[name] = (jax.random.normal(sub, sh, jnp.float32) * 0.1
                         ).astype(dt)
    return out
