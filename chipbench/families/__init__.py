"""Model families, and what the harness takes from a family module.

A configuration names its family by ``family``: a module of this
package (``dense``). Every family module defines ``make_weights``, ``program_params``,
``gap_fn`` and ``np_tokens`` (see ``dense.py``). It may also define how
the benchmark prices and reads its work; where it defines none of
these, the dense counts of ``work.py`` and ``layer_work.py`` hold and no
operation has a layer scope:

- ``SCOPES``: the layer names (``jax.named_scope``) the program gives
  this family's operations, innermost first on an operation's path
  (``spans.scope_of``);
- ``Shapes``: a class with ``from_config(c)``, the sizes its work
  functions read;
- ``decode_work(shapes, positions, args)``: one decode dispatch, each
  live row at absolute position ``p``;
- ``prefill_work(shapes, n, history, args)``: one prefill (``history``
  0) or prefill-chunk dispatch of ``n`` tokens;
- ``decode_attention_work(shapes, keys, rows)``: the decode attention
  of the summed ``keys`` and ``rows`` of the traced decode dispatches;
- ``kv_write_work(shapes, new_tokens)``: the keys and values of the
  summed ``new_tokens`` written once.

Each returns a ``work.Work``. ``args`` holds the integer arguments of
the dispatch's own span (``engine.dispatch.<kind>``), whatever the
program puts there: empty where the program's telemetry is off.

It may also define how ``system.arch_config`` checks that the program
runs what the configuration states; where it defines neither, the dense
decoder's checks of ``arch_check.py`` hold:

- ``WIDTHS``: configuration key -> the program's ``ArchConfig`` field,
  each of which must equal the key's number (a family whose published
  key means another field than the dense one maps it here);
- ``check_arch(c, arch)``: raises ``ValueError`` where the program's
  ``arch`` would run something else than ``c`` states.
"""
from __future__ import annotations

import importlib
from functools import partial

from chipbench import layer_work as LW
from chipbench import work as W

_DEFAULT_WORK = {
    "decode_work": lambda s, positions, args: W.decode(s, positions),
    "prefill_work": lambda s, n, history, args: W.prefill(s, n, history),
    "decode_attention_work": LW.decode_attention,
    "kv_write_work": LW.kv_write,
}


def load(c: dict):
    """The family module that configuration ``c`` names."""
    return importlib.import_module(f"{__name__}.{c['family']}")


class Needed:
    """The needed work and the layer scopes of one configuration, from
    its family module: ``scopes``, ``shapes``, and each work function
    of the module docstring with the shapes bound (``decode_work(
    positions, args)``, ``kv_write_work(new_tokens)``, ...)."""

    def __init__(self, family, c: dict):
        self.scopes = tuple(getattr(family, "SCOPES", ()))
        self.shapes = getattr(family, "Shapes", W.Shapes).from_config(c)
        for name, default in _DEFAULT_WORK.items():
            setattr(self, name,
                    partial(getattr(family, name, default), self.shapes))
