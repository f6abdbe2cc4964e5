"""The plain float32 reference against the serving program's model code
at a tiny size on the CPU: the same weights give the same logits, with
GQA and with a sliding window that masks."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import system
from chipbench.families import dense
from chipbench.tests import tiny


@pytest.mark.parametrize("kv_heads,window", [(4, None), (2, None), (2, 12)],
                         ids=["mha", "gqa", "gqa-window"])
def test_reference_matches_program_forward(kv_heads, window):
    c = tiny.config(kv_heads=kv_heads, window=window)
    arch = tiny.base_arch(c)
    from repro.models import model as MD
    w = dense.make_weights(c, 7, dtype=jnp.float32)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 40),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = dense.forward_logits(w, c, toks)
        got = MD.forward(dense.program_params(w), arch,
                         {"tokens": toks[None]}, attn_impl="reference")
    got = got[0] if isinstance(got, tuple) else got
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_window_masks_in_the_reference():
    c = tiny.config(kv_heads=2, window=12)
    w = dense.make_weights(c, 3, dtype=jnp.float32)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, 40),
                       jnp.int32)
    a = dense.forward_logits(w, c, toks)
    b = dense.forward_logits(w, dict(c, sliding_window=None), toks)
    assert np.allclose(a[:12], b[:12], atol=1e-5)
    assert not np.allclose(a[20:], b[20:], atol=1e-2)


def test_fp8_control_moves_the_logits():
    c = tiny.config()
    w = dense.make_weights(c, 5, dtype=jnp.float32)
    toks = jnp.asarray(np.arange(30) % 256, jnp.int32)
    a = dense.forward_logits(w, c, toks)
    b = dense.forward_logits(w, c, toks, quant=True)
    rel = float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(a ** 2)))
    assert 1e-3 < rel < 0.5


def test_weights_are_seeded_and_laid_out_for_the_program():
    c = tiny.config()
    a = dense.make_weights(c, 2**40 + 3, dtype=jnp.bfloat16)
    b = dense.make_weights(c, 2**40 + 3, dtype=jnp.bfloat16)
    d = dense.make_weights(c, 3, dtype=jnp.bfloat16)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(a))
    assert np.array_equal(np.asarray(a["wq"], np.float32),
                          np.asarray(b["wq"], np.float32))
    assert not np.array_equal(np.asarray(a["wq"], np.float32),
                              np.asarray(d["wq"], np.float32))
    system.import_program()
    from repro.models import model as MD
    want = jax.eval_shape(lambda: MD.init_params(
        jax.random.PRNGKey(0), tiny.base_arch(dict(c, torch_dtype="bfloat16"))))
    got = jax.eval_shape(lambda: dense.program_params(a))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(x.shape == y.shape and x.dtype == y.dtype for x, y in
               zip(jax.tree.leaves(want), jax.tree.leaves(got)))
