"""Distribution config: sharding rules + a reduced-mesh dry-run in a
subprocess (8 placeholder devices — the only place tests override the
device count)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

# the children compile on CPU placeholder devices; pinned here rather
# than inherited, so a parent on a chip machine cannot hand them the TPU
CPU_CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")

SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from functools import partial

from repro.configs import registry
from repro.distributed import hints
from repro.distributed import sharding as SH
from repro.launch import steps as ST
from repro.launch.mesh import make_mesh
from repro.models import model as MD
from repro.optim import AdamW, OptConfig

out = {}
for arch in %(archs)s:
    cfg = registry.get_smoke_config(arch)
    mesh = make_mesh(%(mesh)s, %(axes)s)
    with hints.use_mesh(mesh):
        params_shape = jax.eval_shape(
            partial(MD.init_params, cfg=cfg), jax.random.PRNGKey(0))
        p_sh = SH.param_shardings(mesh, params_shape)
        opt = AdamW(OptConfig())
        opt_shape = jax.eval_shape(opt.init, params_shape)
        o_sh = SH.opt_state_shardings(mesh, opt_shape)
        batch = MD.batch_spec(cfg, 8, 32, "train")
        b_sh = SH.batch_shardings(mesh, batch)
        step = ST.build_train_step(cfg, opt)
        lowered = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh)).lower(
            params_shape, opt_shape, batch)
        compiled = lowered.compile()
        out[arch] = compiled.memory_analysis().peak_memory_in_bytes
print("RESULT " + json.dumps(out))
"""


def run_sub(archs, mesh, axes):
    script = SUBPROCESS_SCRIPT % {
        "archs": repr(archs), "mesh": repr(mesh), "axes": repr(axes)}
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=900,
                       env=CPU_CHILD_ENV)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_train_step_compiles_on_8dev_mesh_dense_and_moe():
    out = run_sub(["qwen1.5-0.5b", "deepseek-moe-16b"], (2, 4),
                  ("data", "model"))
    assert set(out) == {"qwen1.5-0.5b", "deepseek-moe-16b"}
    assert all(v > 0 for v in out.values())


@pytest.mark.slow
def test_train_step_compiles_on_multipod_8dev_mesh():
    out = run_sub(["zamba2-2.7b"], (2, 2, 2), ("pod", "data", "model"))
    assert out["zamba2-2.7b"] > 0


SERVE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from functools import partial

from repro.configs import registry
from repro.distributed import hints
from repro.distributed import sharding as SH
from repro.launch import steps as ST
from repro.launch.mesh import make_mesh
from repro.models import model as MD

out = {}
for arch in %(archs)s:
    cfg = registry.get_smoke_config(arch)
    mesh = make_mesh(%(mesh)s, %(axes)s)
    with hints.use_mesh(mesh):
        params_shape = jax.eval_shape(
            partial(MD.init_params, cfg=cfg), jax.random.PRNGKey(0))
        p_sh = SH.param_shardings(mesh, params_shape, serve=True)
        # serve mode must empty the FSDP axes for a smoke model: row
        # weights shard OUT over model only, nothing over data
        flat = jax.tree_util.tree_flatten_with_path(p_sh)[0]
        row = [s for p, s in flat
               if str(getattr(p[-1], "key", "")) in ("wo", "w_down")]
        assert row, "no row-parallel weights found"
        assert all("data" not in jax.tree.leaves(
            [ax for ax in s.spec if ax is not None]) for s in row), \
            f"serve-mode row weights sharded over data: {row[0].spec}"
        tokens = MD.batch_spec(cfg, 8, 1, "decode")["tokens"]
        t_sh = SH.batch_shardings(mesh, tokens)
        cache_shape = MD.cache_spec(cfg, 8, 64)
        c_sh = SH.cache_shardings(mesh, cache_shape, cfg)
        step = ST.build_serve_step(cfg)
        compiled = jax.jit(step, in_shardings=(p_sh, t_sh, c_sh),
                           out_shardings=(t_sh, None, c_sh),
                           donate_argnums=(2,)).lower(
            params_shape, tokens, cache_shape).compile()
        out[arch] = compiled.memory_analysis().peak_memory_in_bytes
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_serve_decode_step_compiles_on_8dev_mesh():
    """Serve-mode sharding (empty FSDP axes, OUT-over-model row weights)
    lowers and compiles a decode step on a real (2, 4) device world —
    the launch-layer mirror of the mesh serving engine's layout."""
    script = SERVE_SCRIPT % {
        "archs": repr(["qwen1.5-0.5b", "deepseek-moe-16b"]),
        "mesh": repr((2, 4)), "axes": repr(("data", "model"))}
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=900,
                       env=CPU_CHILD_ENV)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert all(v > 0 for v in out.values())


# ---------------------------------------------------------------------------
# pure sharding-rule properties (no devices needed: mesh (1,1))
# ---------------------------------------------------------------------------

def test_param_spec_rules_divisibility():
    """Rules never propose a sharding that doesn't divide the dim — on a
    1x1 mesh everything divides; the 8-device subprocess covers real
    splits. Here we check rule *selection* via the internal helper."""
    import jax
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))

    class Leaf:
        def __init__(self, shape):
            self.shape = shape
            self.ndim = len(shape)

    fsdp = SH.fsdp_axes(mesh)
    # column-parallel: out dim on model
    spec = SH._param_spec(["layers", "attn", "wq"], Leaf((64, 128)), mesh,
                          fsdp)
    assert spec[-1] == "model" or spec[-1] is None
    # 1-D: replicated
    spec = SH._param_spec(["final_norm", "w"], Leaf((64,)), mesh, fsdp)
    assert all(s is None for s in spec)


def test_dryrun_cells_cover_all_archs():
    from repro.configs import registry
    cells = registry.cells()
    archs = {a for a, _ in cells}
    assert len(archs) == 10
    assert len(cells) == 33  # 40 - 7 long_500k skips (full attention)
    # long_500k runs only for the sub-quadratic archs
    long_archs = {a for a, s in cells if s == "long_500k"}
    assert long_archs == {"h2o-danube-1.8b", "xlstm-350m", "zamba2-2.7b"}
