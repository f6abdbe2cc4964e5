"""Pallas kernels for the paper's hot spots (split-KV decode attention,
flash/prefill-over-cache attention, int4 GEMV, fused RMSNorm).

Every kernel entry point takes ``interpret=None``, resolved by
:func:`interpret_mode` — the one place that decides how a kernel runs.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Pallas interpret mode for a kernel call: the explicit ``interpret``
    when a caller gives one (tests compiling for a described chip pass
    False), otherwise interpreted exactly when the default backend is not
    a TPU. On a TPU backend the default is always the compiled kernel."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
