"""A family module for the harness's tests, as a family with routed
experts would bring one: the dense family's weights and reference, a
layer scope of its own (``moe``), and needed work of its own that reads
an argument the dense program does not put on its dispatch spans
(``routed``: tokens routed to this chip's experts) or, where that is
absent, what it does: a decode's ``keys``, a prefill's tokens."""
from __future__ import annotations

from dataclasses import dataclass

from chipbench.families.dense import (gap_fn, make_weights,  # noqa: F401
                                      np_tokens, program_params)
from chipbench.work import Work

SCOPES = ("attention", "moe")
EXPERT_FLOPS = 1000.0       # operations of one routed token in one layer


@dataclass(frozen=True)
class Shapes:
    layers: int

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(layers=int(c["num_hidden_layers"]))


def decode_work(s: Shapes, positions, args: dict) -> Work:
    routed = args.get("routed", args.get("keys", 0))
    return Work(EXPERT_FLOPS * s.layers * routed, 0.0)


def prefill_work(s: Shapes, n: int, history: int, args: dict) -> Work:
    return Work(EXPERT_FLOPS * s.layers * args.get("routed", n), 0.0)


def decode_attention_work(s: Shapes, keys: int, rows: int) -> Work:
    return Work(0.0, 100.0 * s.layers * (keys - rows))
