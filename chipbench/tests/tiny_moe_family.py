"""A routed-expert family for the harness's tests, with a weight layout
of its own: the program's MoE parameter tree (``repro.models.model``,
``repro.models.moe``), in DeepSeekMoE's configuration keys
(huggingface.co/deepseek-ai/deepseek-moe-16b-base). The first
``first_k_dense_replace`` layers are dense SwiGLU layers of
``intermediate_size``; every later layer replaces the MLP by a softmax
router over ``n_routed_experts`` experts of ``moe_intermediate_size``,
of which each token takes the top ``num_experts_per_tok`` (weights
renormalised where ``norm_topk_prob``), plus ``n_shared_experts``
shared experts that every token takes, held as one SwiGLU of their
summed width.

Its checks compare the expert keys with the program, whose registry
holds the dense layer's width as ``d_ff_first_dense``, and refuse what
the program would run otherwise: a router that drops tokens past a
capacity, or weights it renormalises where the file says not. Its
reference is plain float32 of its own: every expert on every token,
weighted by the router's top-k.

``config()`` is a configuration at the smoke widths of the registry's
``deepseek-moe-16b``, and ``base_arch(c)`` the program's model for it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import arch_check
from chipbench.families import dense
from chipbench.families.dense import key_from_seed, np_tokens  # noqa: F401

NAME = "tiny_moe_family"
# the program names no ``moe`` scope yet: its routed layer runs inside
# the ``mlp`` scope (PERF.md, Open questions)
SCOPES = ("attention", "kv_write", "moe", "mlp", "logits", "attn_proj")

# configuration key -> the program's ArchConfig field
WIDTHS = {**arch_check.WIDTHS,
          "intermediate_size": "d_ff_first_dense",
          "moe_intermediate_size": "d_ff_expert",
          "n_routed_experts": "n_experts",
          # the program holds every routed expert on each chip
          "experts_held": "n_experts",
          "num_experts_per_tok": "moe_top_k",
          "n_shared_experts": "n_shared_experts",
          "first_k_dense_replace": "first_dense_layers"}


def check_arch(c: dict, arch) -> None:
    """The decoder's checks, then the router's: softmax scores, a MoE
    layer after every dense one, top-k weights renormalised (the
    program always renormalises), and no routed token dropped."""
    arch_check.check_arch(c, arch)
    if c.get("scoring_func", "softmax") != "softmax" \
            or int(c.get("moe_layer_freq", 1)) != 1:
        raise ValueError("the program routes by softmax in every layer "
                         "after the dense ones")
    if not c["norm_topk_prob"]:
        raise ValueError("norm_topk_prob=False but the program "
                         "renormalises the top-k weights")
    if arch.moe_capacity_factor * arch.moe_top_k < arch.n_experts:
        raise ValueError(
            f"moe_capacity_factor={arch.moe_capacity_factor} drops routed "
            f"tokens past a capacity (none from "
            f"{arch.n_experts / arch.moe_top_k} up); the reference drops "
            f"none")


def config(kind: str = "contig", dtype: str = "float32") -> dict:
    """A configuration at the smoke widths of ``deepseek-moe-16b``, one
    dense layer then two MoE layers, for a CPU test."""
    if kind == "paged":
        engine = {"kv_cache": "paged", "kv_block_size": 16,
                  "scheduler": "chunked", "chunk_tokens": 32,
                  "max_batch": 4, "max_seq_len": 128, "sample": "greedy"}
    else:
        engine = {"kv_cache": "contiguous", "scheduler": "blocking",
                  "max_batch": 4, "max_seq_len": 128, "sample": "greedy"}
    return {"hidden_size": 64, "intermediate_size": 128,
            "moe_intermediate_size": 64, "num_hidden_layers": 3,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "head_dim": 16, "vocab_size": 256, "n_routed_experts": 4,
            "experts_held": 4, "num_experts_per_tok": 2,
            "n_shared_experts": 1, "first_k_dense_replace": 1,
            "moe_layer_freq": 1, "scoring_func": "softmax",
            "norm_topk_prob": True, "rms_norm_eps": 1e-6,
            "rope_theta": 10000.0, "sliding_window": None,
            "tie_word_embeddings": False, "torch_dtype": dtype,
            "family": NAME, "registry_arch": "deepseek-moe-16b",
            "engine": engine,
            "check": {"max_logit_gap": 0.05, "sample_requests": 3}}


def base_arch(c: dict, capacity_factor: float | None = None):
    """The program's smoke-sized ``deepseek-moe-16b`` at the depth of
    ``c``, with a capacity factor at which no routed token is dropped
    (``n_experts / top_k``) unless ``capacity_factor`` is given."""
    from chipbench import system
    system.import_program()
    from repro.configs import registry
    arch = registry.get_smoke_config(c["registry_arch"])
    return arch.replace(
        n_layers=int(c["num_hidden_layers"]), dtype=c["torch_dtype"],
        moe_capacity_factor=capacity_factor or arch.n_experts / arch.moe_top_k)


def dims(c: dict) -> dict:
    m = dense.dims(c)
    m.update(first=int(c["first_k_dense_replace"]),
             E=int(c["n_routed_experts"]), k=int(c["num_experts_per_tok"]),
             Fe=int(c["moe_intermediate_size"]),
             Fs=int(c["n_shared_experts"]) * int(c["moe_intermediate_size"]),
             norm_topk=bool(c["norm_topk_prob"]))
    return m


def leaf_shapes(c: dict) -> dict:
    """Name -> shape of every weight: the dense layers' (``f_``) and the
    MoE layers' each stacked on axis 0."""
    m = dims(c)
    d, H, KV, dh, V = (m[k] for k in ("d", "H", "KV", "dh", "V"))
    n = m["L"] - m["first"]
    out = {"embed": (V, d), "head": (V, d), "final_norm": (d,)}
    for pre, layers in (("f_", m["first"]), ("", n)):
        out.update({pre + "ln1": (layers, d), pre + "ln2": (layers, d),
                    pre + "wq": (layers, d, H * dh),
                    pre + "wk": (layers, d, KV * dh),
                    pre + "wv": (layers, d, KV * dh),
                    pre + "wo": (layers, H * dh, d)})
    out.update({"f_w_gate": (m["first"], d, m["F"]),
                "f_w_up": (m["first"], d, m["F"]),
                "f_w_down": (m["first"], m["F"], d),
                "router": (n, d, m["E"]),
                "w_gate": (n, m["E"], d, m["Fe"]),
                "w_up": (n, m["E"], d, m["Fe"]),
                "w_down": (n, m["E"], m["Fe"], d),
                "s_gate": (n, d, m["Fs"]), "s_up": (n, d, m["Fs"]),
                "s_down": (n, m["Fs"], d)})
    return out


def make_weights(c: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights from ``seed`` in one jitted call; the router in
    float32, as the program holds it."""
    shapes = tuple(sorted(leaf_shapes(c).items()))
    return _draw(key_from_seed(seed), shapes, jnp.dtype(dtype))


@partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shapes, dtype):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith(("ln1", "ln2", "final_norm")):
            x = 1.0 + 0.1 * x
        elif name != "embed":
            x = x / math.sqrt(shape[-1] if name == "head" else shape[-2])
        out[name] = x.astype(jnp.float32 if name == "router" else dtype)
    return out


def program_params(w: dict) -> dict:
    """The same arrays in the program's MoE parameter tree (the leading
    dense layers sliced apart, as the program keeps them in a list)."""
    def layer(pre, ix):
        return {"ln1": {"w": ix(w[pre + "ln1"])},
                "ln2": {"w": ix(w[pre + "ln2"])},
                "attn": {k: ix(w[pre + k]) for k in ("wq", "wk", "wv", "wo")}}
    first = []
    for i in range(w["f_ln1"].shape[0]):
        p = layer("f_", lambda x, i=i: x[i])
        p["mlp"] = {k: w["f_" + k][i] for k in ("w_gate", "w_up", "w_down")}
        first.append(p)
    moe = layer("", lambda x: x)
    moe["moe"] = {"router": w["router"],
                  **{k: w[k] for k in ("w_gate", "w_up", "w_down")},
                  "shared": {"w_gate": w["s_gate"], "w_up": w["s_up"],
                             "w_down": w["s_down"]}}
    return {"embed": {"table": w["embed"]}, "head": w["head"],
            "final_norm": {"w": w["final_norm"]}, "first_layers": first,
            "layers": moe}


# ---------------------------------------------------------------------------
# reference forward pass
# ---------------------------------------------------------------------------

def _swiglu(h, g, u, dn, quant):
    mm = dense._mm
    return mm(jax.nn.silu(mm(h, g, quant)) * mm(h, u, quant), dn, quant)


def _routed(h, lw, m, quant):
    """The routed experts and the shared ones on ``h`` (T, d)."""
    probs = jax.nn.softmax(dense._mm(h, lw["router"], quant), -1)
    top, ids = jax.lax.top_k(probs, m["k"])
    if m["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], ids].set(top)        # (T, E)
    every = jax.vmap(lambda g, u, dn: _swiglu(h, g, u, dn, quant))(
        lw["w_gate"], lw["w_up"], lw["w_down"])              # (E, T, d)
    return (jnp.einsum("te,etd->td", gate, every)
            + _swiglu(h, lw["s_gate"], lw["s_up"], lw["s_down"], quant))


def forward_logits(w: dict, c: dict, tokens, *, quant: bool = False):
    """Logits (T, V) float32 at every position of ``tokens`` (T,)."""
    m = dims(c)
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = w["embed"][tokens].astype(jnp.float32)
    mm = dense._mm

    def attention(x, lw):
        h = dense._rmsnorm(x, lw["ln1"], m["eps"])
        q = mm(h, lw["wq"], quant).reshape(t, m["H"], m["dh"])
        k = mm(h, lw["wk"], quant).reshape(t, m["KV"], m["dh"])
        v = mm(h, lw["wv"], quant).reshape(t, m["KV"], m["dh"])
        q, k = dense._rope(q, pos, m["theta"]), dense._rope(k, pos, m["theta"])
        a = dense._attention(q, k, v, m["window"]).reshape(t, -1)
        x = x + mm(a, lw["wo"], quant)
        return x, dense._rmsnorm(x, lw["ln2"], m["eps"])

    def dense_layer(x, lw):
        x, h = attention(x, lw)
        return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"],
                           quant), None

    def moe_layer(x, lw):
        x, h = attention(x, lw)
        return x + _routed(h, lw, m, quant), None

    first = {k[2:]: v for k, v in w.items() if k.startswith("f_")}
    rest = {k: v for k, v in w.items()
            if k not in ("embed", "head", "final_norm")
            and not k.startswith("f_")}
    x, _ = jax.lax.scan(dense_layer, x, first)
    x, _ = jax.lax.scan(moe_layer, x, rest)
    x = dense._rmsnorm(x, w["final_norm"], m["eps"])
    return mm(x, w["head"].T, quant)


def gap_fn(c: dict, control: bool = False):
    """As ``dense.gap_fn``, with this family's reference."""
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
