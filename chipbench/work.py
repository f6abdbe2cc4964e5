"""The work each dispatch's algorithm needs, from the configuration's
shapes and the live lengths: floating-point operations and HBM bytes.

These count what the computation requires, whatever implements it, so a
faster kernel cannot change them:

- every matrix product once per token (2 operations per weight), the
  output head once per logits row;
- causal attention over the keys each query may see (``min(context,
  window)``): 2 * 2 * heads * head_dim operations per (query, key);
- weights read once per dispatch (every layer, the final norm and the
  head; only the gathered embedding rows), the key/value history of
  live positions read once, and new keys and values written once.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shapes:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    window: int | None = None
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
        return cls(d=d, layers=int(c["num_hidden_layers"]), heads=h,
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c.get("head_dim") or d // h),
                   ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   window=c.get("sliding_window"),
                   dtype_bytes=2 if c.get("torch_dtype") in (
                       "bfloat16", "float16") else 4)

    @property
    def layer_matmul_params(self) -> int:
        """Weights of one layer's matrix products."""
        d, hd = self.d, self.head_dim
        return (d * self.heads * hd + 2 * d * self.kv_heads * hd
                + self.heads * hd * d + 3 * d * self.ff)

    @property
    def weight_bytes(self) -> int:
        """Weights a dispatch reads: all layers (with their two norms),
        the final norm and the head."""
        per_layer = self.layer_matmul_params + 2 * self.d
        n = self.layers * per_layer + self.d + self.vocab * self.d
        return n * self.dtype_bytes

    @property
    def kv_bytes_per_position(self) -> int:
        return (self.layers * 2 * self.kv_heads * self.head_dim
                * self.dtype_bytes)

    def _ctx(self, n: int) -> int:
        return n if self.window is None else min(n, self.window)

    def _attn_flops(self, keys: int) -> int:
        return 4 * self.layers * self.heads * self.head_dim * keys


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, other: "Work") -> "Work":
        self.flops += other.flops
        self.bytes += other.bytes
        return self

    def least_seconds(self, peak_flops: float, peak_bytes_s: float) -> float:
        """The roofline's least time: the larger of the two bounds."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_s)


def decode(s: Shapes, positions) -> Work:
    """One decode dispatch: each live row feeds one token at absolute
    position ``p`` (attending ``p`` cached keys and itself)."""
    n = len(positions)
    flops = n * (2 * s.layers * s.layer_matmul_params + 2 * s.vocab * s.d)
    flops += sum(s._attn_flops(s._ctx(int(p) + 1)) for p in positions)
    hist = sum(s._ctx(int(p) + 1) - 1 for p in positions)
    byt = (s.weight_bytes + n * s.d * s.dtype_bytes
           + hist * s.kv_bytes_per_position + n * s.kv_bytes_per_position)
    return Work(float(flops), float(byt))


def prefill(s: Shapes, n: int, history: int = 0) -> Work:
    """One prefill (``history`` 0) or prefill-chunk dispatch: ``n``
    prompt tokens at positions ``history .. history + n - 1``, each
    attending the keys before it and itself, one logits row."""
    flops = n * 2 * s.layers * s.layer_matmul_params + 2 * s.vocab * s.d
    flops += sum(s._attn_flops(s._ctx(history + j + 1)) for j in range(n))
    byt = (s.weight_bytes + n * s.d * s.dtype_bytes
           + s._ctx(history) * s.kv_bytes_per_position
           + n * s.kv_bytes_per_position)
    return Work(float(flops), float(byt))
