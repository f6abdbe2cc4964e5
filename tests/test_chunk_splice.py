"""The paged chunk splice: a prefill chunk lands in the block pool one
lane group of blocks at a time (``PagedCache.chunk_plan`` +
``model.write_pool_chunk``), bit for bit what the per-token writer
``model.write_pool_positions`` writes, and the engine reports how many
groups its chunk splices rewrote."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as MD
from repro.serving import EngineConfig, ServingEngine, Telemetry
from repro.serving.kv_cache import PagedCache

NB, BS = 384, 16          # three lane groups of 128 blocks
CFG = registry.get_smoke_config("qwen1.5-0.5b").replace(
    n_layers=2, n_kv_heads=2, d_head=8)

# name: (offset, chunk tokens, valid tokens, the chunk's pool blocks;
# None: drawn from an allocator that freed blocks out of order)
CASES = {
    "block_aligned": (32, 64, 64, [10, 11, 12, 13]),
    "unaligned_offset": (7, 64, 64, [40, 41, 42, 43, 44]),
    "pad_tail": (48, 64, 23, [90, 91]),
    "two_groups": (16, 64, 64, [126, 127, 128, 129]),
    "three_groups": (3, 64, 60, [5, 200, 300, 6]),
    "freed_out_of_order": (5, 64, 64, None),
}


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)


def _cache(dtype):
    cache = PagedCache(CFG.replace(dtype=dtype), EngineConfig(
        max_batch=2, max_seq_len=1024, kv_cache="paged", kv_block_size=BS,
        kv_blocks=NB))
    shape = cache._pool_k.shape
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    cache._pool_k = jax.random.normal(k0, shape).astype(cache._pool_k.dtype)
    cache._pool_v = jax.random.normal(k1, shape).astype(cache._pool_v.dtype)
    return cache


def _rows(dtype, s, seed):
    """Chunk rows with every awkward bit pattern among them."""
    x = np.array(jax.random.normal(jax.random.PRNGKey(seed),
                                   (CFG.n_layers, 1, s, 2, 8)), np.float32)
    x[0, 0, 0, 0, :4] = [-0.0, np.inf, -np.inf, np.nan]
    x[1, 0, s - 1, 1, 4:] = [-0.0, 0.0, 1e-40, -1e-40]
    return jnp.asarray(x).astype(dtype)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_chunk_writer_matches_per_token_writer(case, dtype):
    offset, s, n_valid, blocks = CASES[case]
    cache = _cache(dtype)
    first = offset // BS
    if blocks is None:
        held = [cache.allocator.alloc() for _ in range(300)]
        for b in held[20:23] + held[250:252]:
            cache.allocator.free(b)
    else:
        cache.table[0, first:first + len(blocks)] = blocks
    rk, rv = _rows(dtype, s, 1), _rows(dtype, s, 2)
    before = (cache._pool_k, cache._pool_v)
    want_k, want_v = (jnp.array(p) for p in before)
    cache.splice_partial(rk, rv, 0, offset, n_valid)

    pos = offset + np.arange(s)
    blk = np.full(s, NB, np.int32)
    blk[:n_valid] = cache.table[0, pos[:n_valid] // BS]
    idx = (jnp.asarray(blk)[None], jnp.asarray(pos % BS, np.int32)[None])
    want_k = MD.write_pool_positions(want_k, rk, *idx)
    want_v = MD.write_pool_positions(want_v, rv, *idx)
    np.testing.assert_array_equal(_bits(cache._pool_k), _bits(want_k))
    np.testing.assert_array_equal(_bits(cache._pool_v), _bits(want_v))

    used = cache.table[0, first:-(-(offset + n_valid) // BS)]
    if blocks is None:    # handed back last-freed first, across groups
        assert list(used) == [251, 250, 22, 21, 20]
    assert cache.chunk_splice_groups == len(np.unique(used // 128))
    assert cache.chunk_splice_groups == {"two_groups": 2,
                                         "three_groups": 3,
                                         "freed_out_of_order": 2}.get(case, 1)


def test_chunk_plan_lanes_name_each_block_once():
    cache = _cache("float32")
    cache.table[0, :5] = [383, 0, 129, 130, 255]
    lanes, n = cache.chunk_plan(0, 3, 70)
    assert n == 3 and lanes.shape == (3, 128)
    got = {int(g * 128 + j): int(lanes[g, j])
           for g, j in zip(*np.nonzero(lanes >= 0))}
    assert got == {383: 0, 0: 1, 129: 2, 130: 3, 255: 4}


@pytest.mark.parametrize("kv_blocks,block,group,lane", [
    (8, 7, 0, 7),          # fewer than 128 blocks: one group of them all
    (1024, 1000, 7, 104),
    (200, 199, 1, 127),    # a last partial group ends at the pool's end
    (200, 100, 0, 100),
])
def test_chunk_plan_lane_groups_cover_the_pool(kv_blocks, block, group,
                                               lane):
    cache = PagedCache(CFG, EngineConfig(
        max_batch=2, max_seq_len=64, kv_cache="paged", kv_block_size=BS,
        kv_blocks=kv_blocks))
    gw, starts = MD.lane_groups(kv_blocks)
    assert starts[0] == 0 and starts[-1] + gw == kv_blocks
    cache.table[0, :1] = [block]
    lanes, n = cache.chunk_plan(0, 0, 16)
    assert lanes.shape == (len(starts), gw) and n == 1
    assert starts[group] + lane == block and lanes[group, lane] == 0
    assert (lanes >= 0).sum() == 1


@pytest.fixture(scope="module")
def smoke():
    cfg = registry.get_smoke_config("qwen1.5-0.5b").replace(dtype="float32")
    return cfg, MD.init_params(jax.random.PRNGKey(5), cfg)


def _serve(cfg, params, kv_cache, telemetry=None):
    eng = ServingEngine(params, cfg, EngineConfig(
        max_batch=3, max_seq_len=128, max_new_tokens=6, kv_cache=kv_cache,
        kv_block_size=4, scheduler="chunked", chunk_tokens=16),
        telemetry=telemetry)
    rng = np.random.default_rng(7)
    for n in (37, 50, 61, 45, 34):      # each more than two chunks
        eng.submit(rng.integers(0, cfg.vocab_size, size=n))
    eng.run()
    return eng


def test_chunked_paged_engine_matches_contiguous_greedy(smoke):
    cfg, params = smoke
    tel = Telemetry()
    paged = _serve(cfg, params, "paged", tel)
    contiguous = _serve(cfg, params, "contiguous")
    assert ({r.rid: r.output for r in paged.finished}
            == {r.rid: r.output for r in contiguous.finished})
    assert all(r.prefill_chunks >= 3 for r in paged.finished)

    # the splice span's ``groups`` label and the engine's running total
    splices = [s for s in tel.tracer.spans if s.name == "engine.kv.splice"]
    assert len(splices) == paged.prefill_chunk_dispatches
    assert all(s.labels["groups"] >= 1 for s in splices)
    total = paged.summary()["chunk_splice_groups"]
    assert total == sum(s.labels["groups"] for s in splices) >= len(splices)
    assert contiguous.summary()["chunk_splice_groups"] == 0
