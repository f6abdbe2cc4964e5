"""Compile rehearsal of each cell for one TPU v5e, with no chip attached:
the engine's decode program and its largest prefill program, at the
configuration's published widths and the cell's slots and capacity,
compiled for a described ``v5e:2x2`` topology. A cluster cell serves
with one worker on each chip, each the same engine, so the same two
programs are each worker's; beside them the KV handoff's largest
programs: the gather of a prompt's blocks on the prefill chip and their
splice into a decode chip's pool. The compiler refuses here what the
chip would refuse, and ``memory_analysis()`` says whether the program,
with the resident weights and KV cache among its operands or beside
them, fits one chip's HBM.

The topology is described inside a module fixture, never at import
time: only one process may load the TPU library, and the test workers
each import this file.
"""
from __future__ import annotations

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench import families, run, system
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
HBM_BYTES = int(15.75 * 2**30)   # what XLA lets one v5e program use


def _cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]


CELLS = _cells() + [tiny.DISAGG["name"]]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.bench_with(tmp_path_factory.mktemp("bench"), tiny.DISAGG)


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


def _programs(c: dict, mix: dict, sh, base=None):
    """(name, jitted closure, operand shapes, bytes resident beside the
    operands) of the cell's decode program and its largest prefill
    program, and in a cluster its largest KV handoff programs. The
    parameters are laid out by the configuration's family; ``base`` is
    ``system.arch_config``'s."""
    system.import_program()
    from repro.models import model as MD
    from repro.serving import kv_cache as KV
    from repro.serving.engine import CACHE_ARGNUM, build_closures

    arch = system.arch_config(c, base)
    e = c["engine"]
    B, C = int(e["max_batch"]), int(e["max_seq_len"])
    closures = build_closures(arch, C)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    family = families.load(c)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda: family.program_params(family.make_weights(
            c, 0, dtype=c["torch_dtype"]))))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=sh)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sh)
    live = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=sh)
    if e["kv_cache"] == "paged":
        bs = int(e["kv_block_size"])
        cache = jax.tree.map(sds, MD.paged_cache_spec(arch, B, C, bs,
                                                      ragged=False))
        cache["len"] = i32
    else:
        cache = jax.tree.map(sds, MD.cache_spec(arch, B, C))
    decode = ("decode", jax.jit(closures["decode"],
                                donate_argnums=CACHE_ARGNUM),
              (params, toks, cache, pos, live), 0)
    if e["scheduler"] == "chunked":
        ct = int(e["chunk_tokens"])
        batch = {"tokens": jax.ShapeDtypeStruct((1, ct), jnp.int32,
                                                sharding=sh)}
        table = jax.ShapeDtypeStruct((C // int(e["kv_block_size"]),),
                                     jnp.int32, sharding=sh)
        pre = ("chunk_paged", jax.jit(closures["chunk_paged"]),
               (params, batch, cache["k"], cache["v"], table, i32, i32), 0)
    else:
        # the longest prompt of the mix, padded to its bucket where the
        # engine buckets (a power of two, at most the prompt capacity)
        n = int(mix["prompt"]["max"])
        if arch.sliding_window is None:
            n = min(1 << (n - 1).bit_length(), C - 1)
        batch = {"tokens": jax.ShapeDtypeStruct((1, n), jnp.int32,
                                                sharding=sh)}
        kv = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        pre = ("prefill", jax.jit(closures["prefill"]),
               (params, batch, i32, i32), kv)
    out = [decode, pre]
    if c.get("cluster"):
        bs = int(e["kv_block_size"])
        nb = -(-int(mix["prompt"]["max"]) // bs)   # blocks of a handoff
        pool = cache["k"]
        rows = jax.ShapeDtypeStruct(
            (pool.shape[0], 1, nb * bs) + pool.shape[3:], pool.dtype,
            sharding=sh)
        idx = jax.ShapeDtypeStruct((nb,), jnp.int32, sharding=sh)
        weights = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
        half = pool.size * pool.dtype.itemsize
        out += [("gather_blocks", KV.gather_blocks, (pool, idx),
                 weights + half),
                ("splice_blocks", KV.splice_blocks,
                 (pool, cache["v"], rows, rows, idx), weights)]
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_programs_fit_one_chip(one_chip, bench, cell):
    _, c, mix, _, _ = run.load_cell(cell, bench)
    for name, fn, args, resident in _programs(c, mix, one_chip):
        m = fn.lower(*args).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes + resident)
        print(f"{cell} {name}: arguments {m.argument_size_in_bytes} "
              f"output {m.output_size_in_bytes} alias "
              f"{m.alias_size_in_bytes} temp {m.temp_size_in_bytes} "
              f"resident KV beside it {resident} total {total}")
        assert total < HBM_BYTES, (cell, name, total)
