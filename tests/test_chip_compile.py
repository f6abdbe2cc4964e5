"""Compile rehearsal for one TPU v5e chip, with no chip attached: the
Pallas kernels at phi3-mini-3.8b widths and the engine's decode step at
the slots x capacity ``chip_smoke.py`` serves, compiled for a described
``v5e:2x2`` topology. The TPU compiler refuses here what it would refuse
on the chip: misaligned kernel tiles, unsupported kernel ops, programs
that do not fit the chip's HBM.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and the test workers each
import this file.
"""
from __future__ import annotations

import functools
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import decode_attention as DEC
from repro.kernels import flash_attention as FA
from repro.kernels import quant_gemv as QG
from repro.kernels import rmsnorm as RN
from repro.models import model as MD
from repro.serving.engine import CACHE_ARGNUM, build_closures
from repro.serving.kv_cache import contiguous_kv_bytes

HBM_BYTES = int(15.75 * 2**30)   # what XLA lets one v5e program use
PHI3 = registry.get_config("phi3-mini-3.8b")


def _smoke_sizes():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.SLOTS, mod.CAPACITY


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    """(kernel fn, operand shapes) at phi3 widths, in the layout each
    ``kernels/ops.py`` wrapper hands its kernel."""
    b, c, hq, hkv, dh = 8, 2048, PHI3.n_heads, PHI3.n_kv_heads, PHI3.d_head
    g, d, s = hq // hkv, PHI3.d_model, 256
    bs, nb = 16, 8 * 2048 // 16
    i32, u8 = jnp.int32, jnp.uint8
    return {
        "flash_attention": (FA.flash_attention_bhsd, [
            (b * hq, s, dh), (b * hq, s, dh), (b * hq, s, dh)]),
        "decode_attention": (DEC.decode_attention_bhgd, [
            (b, hkv, g, dh), (b, c, hkv, dh), (b, c, hkv, dh), ((b,), i32)]),
        "paged_decode_attention": (DEC.decode_attention_paged_bhgd, [
            (b, hkv, g, dh), (nb, bs, hkv, dh), (nb, bs, hkv, dh),
            ((b, c // bs), i32), ((b,), i32)]),
        "prefill_attention": (FA.flash_attention_hist_bhsd, [
            (hq, s, dh), (hq, c, dh), (hq, c, dh), (hq, s, dh), (hq, s, dh),
            ((hq,), i32)]),
        "quant_gemv": (QG.quant_gemv, [
            (b, d), ((d // 2, PHI3.d_ff), u8), (d // 128, PHI3.d_ff)]),
        "rmsnorm": (RN.rmsnorm, [(b, d), (d,)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e_at_phi3_widths(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [_sds(one_chip, *s) if isinstance(s[0], tuple)
            else _sds(one_chip, s) for s in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_cache", ["contiguous", "paged"])
def test_engine_decode_step_fits_one_chip(one_chip, kv_cache):
    """The served decode step at the smoke's slots x capacity fits the
    chip's HBM, and its new cache aliases the donated resident one (no
    second cache, no cache-sized temporary)."""
    slots, capacity = _smoke_sizes()
    put = functools.partial(jax.tree.map, lambda a: _sds(one_chip, a.shape,
                                                         a.dtype))
    params = put(jax.eval_shape(
        lambda: MD.init_params(jax.random.PRNGKey(0), PHI3)))
    if kv_cache == "contiguous":
        cache = put(MD.cache_spec(PHI3, slots, capacity))
    else:
        cache = put(MD.paged_cache_spec(PHI3, slots, capacity, 16))
    decode = jax.jit(build_closures(PHI3, capacity)["decode"],
                     donate_argnums=CACHE_ARGNUM)
    mem = decode.lower(
        params, _sds(one_chip, (slots, 1), jnp.int32), cache,
        _sds(one_chip, (slots,), jnp.int32),
        _sds(one_chip, (slots,), jnp.bool_)).compile().memory_analysis()
    kv_bytes = contiguous_kv_bytes(PHI3, slots, capacity)
    assert mem.peak_memory_in_bytes <= HBM_BYTES
    assert mem.alias_size_in_bytes >= kv_bytes
    assert mem.temp_size_in_bytes < kv_bytes // 8


@pytest.mark.parametrize("kv_cache", ["contiguous", "paged"])
def test_engine_chunk_step_fits_one_chip(one_chip, kv_cache):
    """A chunked-prefill step (256-token chunk) over the full resident
    cache fits beside it: the slot's history is read one layer at a
    time, never as a cache-sized temporary."""
    slots, capacity = _smoke_sizes()
    put = functools.partial(jax.tree.map, lambda a: _sds(one_chip, a.shape,
                                                         a.dtype))
    params = put(jax.eval_shape(
        lambda: MD.init_params(jax.random.PRNGKey(0), PHI3)))
    i32 = jnp.int32
    scalar = _sds(one_chip, (), i32)
    if kv_cache == "contiguous":
        kv = put(MD.cache_spec(PHI3, slots, capacity))
        where = scalar                                   # the slot
    else:
        kv = put(MD.paged_cache_spec(PHI3, slots, capacity, 16))
        where = _sds(one_chip, (capacity // 16,), i32)   # its table row
    chunk = jax.jit(build_closures(PHI3, capacity)[f"chunk_{kv_cache}"])
    mem = chunk.lower(params, {"tokens": _sds(one_chip, (1, 256), i32)},
                      kv["k"], kv["v"], where, scalar,
                      scalar).compile().memory_analysis()
    kv_bytes = contiguous_kv_bytes(PHI3, slots, capacity)
    assert mem.peak_memory_in_bytes <= HBM_BYTES
    assert mem.temp_size_in_bytes < kv_bytes // 4
