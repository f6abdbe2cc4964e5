"""Dense decoder family (RoPE, RMSNorm, SwiGLU, GQA, optional sliding
window): seeded weights, the serving program's parameter layout, and
the plain float32 reference forward pass.

Nothing here imports the program. The weights are the benchmark's own:
drawn on the device from the seed in one jitted call, in the dtype they
are served in, and handed to the program and the reference alike.

The reference follows the published description of these models
(Llama/Mistral/Phi-3 decoder): pre-norm blocks, RMSNorm
``x * rsqrt(mean(x^2) + eps) * w``, rotary embedding on the two halves
of each head (``rotate_half``), causal attention with a window of
``sliding_window`` positions (a key at distance >= window is masked),
SwiGLU ``w_down(silu(w_gate x) * w_up x)``, an untied output head. It
computes in float32 under ``default_matmul_precision("highest")``,
layer by layer (one layer's weights in float32 at a time) and in blocks
of queries, so it fits beside the served weights on one chip.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # queries per attention block in the reference
# the program's layer scopes for this family (``jax.named_scope`` in
# ``models/model.py``); its needed work is the dense count of ``work.py``
SCOPES = ("attention", "kv_write", "mlp", "logits", "attn_proj")


def dims(c: dict) -> dict:
    """The sizes the weights and the reference need, from a config."""
    d = int(c["hidden_size"])
    h = int(c["num_attention_heads"])
    return {"d": d, "L": int(c["num_hidden_layers"]), "H": h,
            "KV": int(c["num_key_value_heads"]),
            "dh": int(c.get("head_dim") or d // h),
            "F": int(c["intermediate_size"]), "V": int(c["vocab_size"]),
            "eps": float(c["rms_norm_eps"]),
            "theta": float(c["rope_theta"]),
            "window": c.get("sliding_window")}


def leaf_shapes(c: dict) -> dict:
    """Name -> shape of every weight; layer weights stack on axis 0."""
    m = dims(c)
    d, L, H, KV, dh, F, V = (m[k] for k in ("d", "L", "H", "KV", "dh", "F",
                                            "V"))
    return {"embed": (V, d), "head": (V, d), "final_norm": (d,),
            "ln1": (L, d), "ln2": (L, d),
            "wq": (L, d, H * dh), "wk": (L, d, KV * dh),
            "wv": (L, d, KV * dh), "wo": (L, H * dh, d),
            "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d)}


def _scale(name: str, shape) -> float:
    """Standard deviation of each leaf. Projections keep unit-variance
    activations (1/sqrt(fan in)), so attention scores are O(1) and the
    attention pattern matters; the embedding is unit-scale so a token's
    identity survives into the residual stream; norm gains are drawn
    around 1 so a norm that ignores its gain is seen."""
    if name == "embed":
        return 1.0
    if name == "head":
        return 1.0 / math.sqrt(shape[-1])
    if name in ("ln1", "ln2", "final_norm"):
        return 0.1
    return 1.0 / math.sqrt(shape[-2])


def key_from_seed(seed: int):
    """A JAX key from any non-negative integer seed (more than 32 bits
    fold in their high part)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(c: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights from ``seed``, on the default device, in one jitted
    call. Stacked layer leaves are drawn one layer at a time, so the
    float32 draw of a layer is the only transient."""
    shapes = tuple(sorted(leaf_shapes(c).items()))
    return _draw(key_from_seed(seed), shapes, jnp.dtype(dtype))


@partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shapes, dtype):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        s = _scale(name, shape)
        norm = name in ("ln1", "ln2", "final_norm")

        def one(kk, shp=shape[1:] if len(shape) == 3 else shape, s=s,
                norm=norm):
            x = jax.random.normal(kk, shp, jnp.float32) * s
            return ((1.0 + x) if norm else x).astype(dtype)

        if len(shape) == 3:
            out[name] = jax.lax.map(one, jax.random.split(k, shape[0]))
        else:
            out[name] = one(k)
    return out


def program_params(w: dict) -> dict:
    """The same arrays in the serving program's parameter tree (no
    copy): ``repro.models.model`` dense decoder layout."""
    return {"embed": {"table": w["embed"]}, "head": w["head"],
            "final_norm": {"w": w["final_norm"]},
            "layers": {"ln1": {"w": w["ln1"]}, "ln2": {"w": w["ln2"]},
                       "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                       "mlp": {k: w[k] for k in ("w_gate", "w_up",
                                                 "w_down")}}}


# ---------------------------------------------------------------------------
# reference forward pass
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round ``x`` through float8 e4m3 with one scale per slice along
    ``axis`` (absolute max to the format's 448): the arithmetic of an
    fp8 matrix product that accumulates in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(x, w, quant):
    """x (T, k) @ w (k, n) in float32; with ``quant`` both operands are
    first rounded to fp8 (per row of x, per column of w)."""
    w = w.astype(jnp.float32)
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, heads, dh): rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(q, k, v, window):
    """Causal (windowed) attention, queries in blocks of ``Q_BLOCK``.
    q (T, H, dh); k, v (T, KV, dh)."""
    t, h, dh = q.shape
    kv = k.shape[1]
    g = h // kv
    nblk = -(-t // Q_BLOCK)
    pad = nblk * Q_BLOCK - t
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        nblk, Q_BLOCK, kv, g, dh)
    kpos = jnp.arange(t)

    def block(args):
        i, qq = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhgd,khd->hgqk", qq, k) / math.sqrt(dh)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(block, (jnp.arange(nblk), qb))
    return out.reshape(nblk * Q_BLOCK, h, dh)[:t]


def forward_logits(w: dict, c: dict, tokens, *, quant: bool = False):
    """Logits (T, V) float32 at every position of ``tokens`` (T,)."""
    m = dims(c)
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                "w_gate", "w_up", "w_down")}

    def layer(x, lw):
        h = _rmsnorm(x, lw["ln1"], m["eps"])
        q = _mm(h, lw["wq"], quant).reshape(t, m["H"], m["dh"])
        k = _mm(h, lw["wk"], quant).reshape(t, m["KV"], m["dh"])
        v = _mm(h, lw["wv"], quant).reshape(t, m["KV"], m["dh"])
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
        a = _attention(q, k, v, m["window"]).reshape(t, -1)
        x = x + _mm(a, lw["wo"], quant)
        h = _rmsnorm(x, lw["ln2"], m["eps"])
        f = jax.nn.silu(_mm(h, lw["w_gate"], quant)) * _mm(h, lw["w_up"],
                                                            quant)
        return x + _mm(f, lw["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, layers)
    x = _rmsnorm(x, w["final_norm"], m["eps"])
    return _mm(x, w["head"].T, quant)


def gap_fn(c: dict, control: bool = False):
    """Jitted ``(weights, tokens (T,), targets (T,)) -> gaps (T,)``:
    how far the reference's logit of ``targets[t]`` lies below its best
    logit at position ``t``. With ``control``, the second output is the
    same gap for the token an fp8 computation of the reference puts
    first at each position."""
    def run(w, tokens, targets):
        with jax.default_matmul_precision("highest"):
            ref = forward_logits(w, c, tokens)
            best = jnp.max(ref, -1)
            gap = best - jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
            if not control:
                return gap, None
            low = jnp.argmax(forward_logits(w, c, tokens, quant=True), -1)
            cgap = best - jnp.take_along_axis(ref, low[:, None], -1)[:, 0]
            return gap, cgap
    return jax.jit(run)


def np_tokens(prompt, output, length: int):
    """The teacher-forced sequence ``prompt + output[:-1]`` right-padded
    to ``length``, the targets ``output`` placed at the positions that
    produced them, and the index range of those positions."""
    p, o = len(prompt), len(output)
    seq = np.zeros(length, np.int32)
    seq[:p] = prompt
    seq[p:p + o - 1] = output[:-1]
    tgt = np.zeros(length, np.int32)
    tgt[p - 1:p - 1 + o] = output
    return seq, tgt, (p - 1, p - 1 + o)
