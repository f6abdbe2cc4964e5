"""Per-kernel allclose vs the pure-jnp oracle, sweeping shapes/dtypes.

All kernels run in Pallas interpret mode on CPU (bit-accurate w.r.t. the
BlockSpec tiling); the same call dispatches to the compiled TPU kernel
on real hardware.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def rand(key, shape, dtype=jnp.bfloat16, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def tol_for(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,sq,sk,hq,hkv,dh", [
    (1, 128, 128, 4, 4, 64),      # MHA square
    (2, 256, 256, 8, 2, 64),      # GQA 4:1
    (1, 128, 384, 4, 4, 128),     # continuation (q_offset)
    (2, 100, 100, 4, 2, 64),      # ragged (non-multiple of block)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, sq, sk, hq, hkv, dh, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, sq, hq, dh), dtype)
    k = rand(k2, (b, sk, hkv, dh), dtype)
    v = rand(k3, (b, sk, hkv, dh), dtype)
    q_off = sk - sq  # continuation semantics when sk > sq
    got = ops.flash_attention(q, k, v, causal=True, q_offset=q_off,
                              block_q=64, block_k=64)
    # oracle with expanded heads + offset positions
    ke = jnp.repeat(k, hq // hkv, axis=2)
    ve = jnp.repeat(v, hq // hkv, axis=2)
    import math
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        ke.astype(jnp.float32)) / math.sqrt(dh)
    ok = (jnp.arange(sk)[None, :] <= q_off + jnp.arange(sq)[:, None])
    scores = jnp.where(ok, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, ve.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol_for(dtype))


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_window(window):
    k1, k2, k3 = jax.random.split(KEY, 3)
    b, s, h, dh = 1, 256, 4, 64
    q = rand(k1, (b, s, h, dh), jnp.float32)
    k = rand(k2, (b, s, h, dh), jnp.float32)
    v = rand(k3, (b, s, h, dh), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# decode attention (split-KV)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,hq,hkv,dh,clen", [
    (1, 512, 8, 8, 64, 100),
    (2, 1024, 8, 2, 64, 1024),    # GQA, full cache
    (4, 2048, 16, 4, 128, 777),   # ragged length
    (1, 512, 4, 1, 64, 1),        # single valid slot
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(b, s, hq, hkv, dh, clen, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, 1, hq, dh), dtype)
    kc = rand(k2, (b, s, hkv, dh), dtype)
    vc = rand(k3, (b, s, hkv, dh), dtype)
    got = ops.decode_attention(q, kc, vc, jnp.asarray(clen), block_s=256)
    want = ref.decode_attention_ref(q, kc, vc, clen)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol_for(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_per_row_lengths(dtype):
    """Fully-ragged batch: every row masks its own KV span, and each row
    matches the same kernel run at that row's scalar length."""
    b, s, hq, hkv, dh = 4, 512, 8, 2, 64
    lens = jnp.asarray([1, 100, 333, 512], jnp.int32)
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, 1, hq, dh), dtype)
    kc = rand(k2, (b, s, hkv, dh), dtype)
    vc = rand(k3, (b, s, hkv, dh), dtype)
    got = ops.decode_attention(q, kc, vc, lens, block_s=256)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol_for(dtype))
    for i, n in enumerate(np.asarray(lens)):
        row = ops.decode_attention(q[i:i + 1], kc[i:i + 1], vc[i:i + 1],
                                   jnp.asarray(int(n)), block_s=256)
        np.testing.assert_allclose(
            np.asarray(got[i], np.float32), np.asarray(row[0], np.float32),
            **tol_for(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_dense_gather(dtype):
    """The paged split-KV kernel (block pools + scalar-prefetched block
    tables) must match the contiguous kernel run on the densely gathered
    view; sentinel (unallocated) table entries are masked by lens."""
    from repro.models.attention import gather_kv_blocks
    b, hq, hkv, dh, bs, w = 3, 8, 4, 64, 64, 4
    nb = b * w + 2  # a couple of free blocks stay in the pool
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, 1, hq, dh), dtype)
    kp = rand(k2, (nb, bs, hkv, dh), dtype)
    vp = rand(k3, (nb, bs, hkv, dh), dtype)
    perm = np.random.default_rng(0).permutation(nb)[: b * w]
    tab = np.asarray(perm, np.int32).reshape(b, w)
    tab[0, 3] = nb  # unallocated tails (sentinel id == nb)
    tab[1, 2:] = nb
    tab = jnp.asarray(tab)
    lens = jnp.asarray([3 * bs - 5, bs + 7, 4 * bs], jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, tab, lens)
    kd, vd = gather_kv_blocks(kp, tab), gather_kv_blocks(vp, tab)
    want = ops.decode_attention(q, kd, vd, lens, block_s=bs)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol_for(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hist", [0, 7, 40, 96])
def test_prefill_attention_over_cache_matches_reference(dtype, hist):
    """The chunked-prefill entry point (one softmax over cached history
    + causal self) must match the pure-JAX reference for every history
    length including the empty-history first chunk."""
    from repro.models.attention import prefill_over_cache
    b, s, c, hq, hkv, dh = 2, 16, 96, 8, 4, 64
    ks = jax.random.split(KEY, 5)
    q = rand(ks[0], (b, s, hq, dh), dtype)
    kh = rand(ks[1], (b, c, hkv, dh), dtype)
    vh = rand(ks[2], (b, c, hkv, dh), dtype)
    k_self = rand(ks[3], (b, s, hkv, dh), dtype)
    v_self = rand(ks[4], (b, s, hkv, dh), dtype)
    got = ops.prefill_attention(q, kh, vh, jnp.asarray(hist), k_self,
                                v_self)
    want = prefill_over_cache(q, kh, vh, jnp.asarray(hist), k_self, v_self)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol_for(dtype))


def test_prefill_attention_per_row_history_lengths():
    """Per-row history lengths (ragged chunk batch) match per-row
    scalar runs of the same kernel."""
    b, s, c, hq, hkv, dh = 3, 8, 64, 4, 2, 32
    ks = jax.random.split(KEY, 5)
    q = rand(ks[0], (b, s, hq, dh), jnp.float32)
    kh = rand(ks[1], (b, c, hkv, dh), jnp.float32)
    vh = rand(ks[2], (b, c, hkv, dh), jnp.float32)
    k_self = rand(ks[3], (b, s, hkv, dh), jnp.float32)
    v_self = rand(ks[4], (b, s, hkv, dh), jnp.float32)
    lens = jnp.asarray([0, 17, 64], jnp.int32)
    got = ops.prefill_attention(q, kh, vh, lens, k_self, v_self)
    for i, n in enumerate(np.asarray(lens)):
        row = ops.prefill_attention(
            q[i:i + 1], kh[i:i + 1], vh[i:i + 1], jnp.asarray(int(n)),
            k_self[i:i + 1], v_self[i:i + 1])
        np.testing.assert_allclose(
            np.asarray(got[i], np.float32), np.asarray(row[0], np.float32),
            **tol_for(jnp.float32))


# ---------------------------------------------------------------------------
# multi-token verify attention (speculative decoding)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,c,hq,hkv,dh", [
    (2, 5, 96, 8, 4, 64),      # gamma=4 verify window, GQA
    (1, 3, 128, 4, 4, 32),     # MHA
    (3, 8, 64, 8, 2, 64),      # wider window, deeper GQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_attention_matches_ref(b, s, c, hq, hkv, dh, dtype):
    """The speculative verify kernel (gamma+1 candidate tokens per row,
    one softmax over cached history + causal window) vs the pure-jnp
    oracle, scalar history length."""
    ks = jax.random.split(KEY, 5)
    q = rand(ks[0], (b, s, hq, dh), dtype)
    kh = rand(ks[1], (b, c, hkv, dh), dtype)
    vh = rand(ks[2], (b, c, hkv, dh), dtype)
    k_self = rand(ks[3], (b, s, hkv, dh), dtype)
    v_self = rand(ks[4], (b, s, hkv, dh), dtype)
    got = ops.verify_attention(q, kh, vh, jnp.asarray(40), k_self, v_self)
    want = ref.verify_attention_ref(q, kh, vh, jnp.asarray(40), k_self,
                                    v_self)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol_for(dtype))


def test_verify_attention_ragged_per_row_history():
    """Per-row history lengths — every serving slot verifies its
    gamma+1 window at its own absolute position in one call — match
    per-row scalar runs, including empty and full histories."""
    b, s, c, hq, hkv, dh = 4, 4, 64, 4, 2, 32
    ks = jax.random.split(KEY, 5)
    q = rand(ks[0], (b, s, hq, dh), jnp.float32)
    kh = rand(ks[1], (b, c, hkv, dh), jnp.float32)
    vh = rand(ks[2], (b, c, hkv, dh), jnp.float32)
    k_self = rand(ks[3], (b, s, hkv, dh), jnp.float32)
    v_self = rand(ks[4], (b, s, hkv, dh), jnp.float32)
    lens = jnp.asarray([0, 13, 37, 64], jnp.int32)
    got = ops.verify_attention(q, kh, vh, lens, k_self, v_self)
    want = ref.verify_attention_ref(q, kh, vh, lens, k_self, v_self)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for i, n in enumerate(np.asarray(lens)):
        row = ops.verify_attention(
            q[i:i + 1], kh[i:i + 1], vh[i:i + 1], jnp.asarray(int(n)),
            k_self[i:i + 1], v_self[i:i + 1])
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(row[0]), atol=2e-5, rtol=2e-5)


def test_verify_attention_gamma1_degenerates_to_decode_kernel():
    """A 1-token verify window is a decode step: against a dense cache
    holding the same self KV at each row's length, the split-KV decode
    kernel must agree (ragged lengths included)."""
    b, c, hq, hkv, dh = 3, 64, 8, 4, 64
    ks = jax.random.split(KEY, 5)
    q = rand(ks[0], (b, 1, hq, dh), jnp.float32)
    kh = rand(ks[1], (b, c, hkv, dh), jnp.float32)
    vh = rand(ks[2], (b, c, hkv, dh), jnp.float32)
    k_self = rand(ks[3], (b, 1, hkv, dh), jnp.float32)
    v_self = rand(ks[4], (b, 1, hkv, dh), jnp.float32)
    lens = np.asarray([5, 22, 63], np.int32)
    got = ops.verify_attention(q, kh, vh, jnp.asarray(lens), k_self,
                               v_self)
    # dense equivalent: self KV spliced at each row's own position
    kc = np.asarray(kh).copy()
    vc = np.asarray(vh).copy()
    for i, n in enumerate(lens):
        kc[i, n] = np.asarray(k_self)[i, 0]
        vc[i, n] = np.asarray(v_self)[i, 0]
    want = ops.decode_attention(jnp.asarray(np.asarray(q)),
                                jnp.asarray(kc), jnp.asarray(vc),
                                jnp.asarray(lens + 1), block_s=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# int4 quantized GEMV (W4A16 mobile mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,n,group", [
    (1, 256, 512, 128),
    (4, 512, 256, 128),
    (2, 1024, 1024, 256),
])
def test_quant_gemv_matches_ref(b, k, n, group):
    k1, k2 = jax.random.split(KEY)
    x = rand(k1, (b, k), jnp.bfloat16)
    w = rand(k2, (k, n), jnp.float32, scale=0.5)
    packed, scales = ref.quantize_int4(w, group=group)
    got = ops.quant_gemv(x, packed, scales, group=group, block_n=128)
    want = ref.quant_gemv_ref(x, packed, scales, group=group)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=5e-2, rtol=5e-2)


def test_quantize_int4_roundtrip_error_bound():
    """|w - dequant(quant(w))| <= scale/2 per element."""
    w = jax.random.normal(KEY, (512, 128), jnp.float32)
    packed, scales = ref.quantize_int4(w, group=128)
    lo = (packed & 0xF).astype(jnp.int8)
    hi = (packed >> 4).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    wq = jnp.zeros(w.shape, jnp.int8).at[0::2].set(lo).at[1::2].set(hi)
    deq = wq.astype(jnp.float32) * jnp.repeat(scales, 128, axis=0)
    err = np.abs(np.asarray(w - deq))
    bound = np.repeat(np.asarray(scales), 128, axis=0) / 2 + 1e-6
    assert (err <= bound).all()


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,d", [(8, 256), (64, 1024), (3, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(m, d, dtype):
    k1, k2 = jax.random.split(KEY)
    x = rand(k1, (m, d), dtype)
    w = rand(k2, (d,), jnp.float32, scale=0.2) + 1.0
    got = ops.rmsnorm(x, w, block_m=4)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol_for(dtype))


# ---------------------------------------------------------------------------
# kernels vs the model's own attention paths
# ---------------------------------------------------------------------------

def test_chunked_attention_matches_reference_impl():
    from repro.models.attention import chunked_attention, reference_attention
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (2, 300, 8, 64), jnp.float32)
    k = rand(k2, (2, 300, 2, 64), jnp.float32)
    v = rand(k3, (2, 300, 2, 64), jnp.float32)
    got = chunked_attention(q, k, v, causal=True, q_chunk=128, kv_chunk=128)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_pallas_flash_matches_chunked_impl():
    from repro.models.attention import attention
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (1, 256, 4, 64), jnp.float32)
    k = rand(k2, (1, 256, 4, 64), jnp.float32)
    v = rand(k3, (1, 256, 4, 64), jnp.float32)
    got = attention(q, k, v, impl="pallas")
    want = attention(q, k, v, impl="chunked", q_chunk=128, kv_chunk=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_interpret_mode_is_decided_by_the_backend(monkeypatch):
    """Kernels interpret exactly off a TPU; on a TPU they always compile
    unless a caller asks otherwise explicitly."""
    from repro.kernels import interpret_mode
    assert interpret_mode() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    assert interpret_mode(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert interpret_mode() is True
    assert interpret_mode(False) is False
