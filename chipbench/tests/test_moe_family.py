"""A family with its own weight layout through the whole harness, with no
file of the harness edited: the routed-expert family of
``tiny_moe_family.py`` (the program's MoE tree at the smoke widths of
``deepseek-moe-16b``) draws weights the program's closures accept in the
compile rehearsal, serves ``correct`` through ``run.run`` on the CPU,
fails the check when its routing is broken, has a traced run's metric
file read its ``moe`` scope, and is refused by ``system.arch_config``
where its expert keys differ from the program."""
from __future__ import annotations

import importlib.util
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench import run, system
from chipbench import spans as SP
from chipbench import trace as TR
from chipbench.tests import test_compile_rehearsal as rehearsal
from chipbench.tests import test_faults, tiny
from chipbench.tests import tiny_moe_family as F

NAMES = ["ttft_p95_s", "itl_p95_ms", "output_tok_s", "setup_s"]
LOOPS = {"contig": "closed", "paged": "open"}
QUIET = {"log": lambda *a, **k: None}

# a metric a routed-expert family could add as a file of its own: device
# time of its ``moe`` scope per decode dispatch of the traced stretch
MOE_METRIC = '''
def read(rec):
    if rec.spans is None:
        return None
    n = rec.spans["dispatch"].get("decode", {}).get("n", 0)
    t = rec.spans["device_s"].get("decode", {}).get("moe", 0.0)
    return 1e6 * t / n if n and t > 0 else None
'''


@pytest.fixture(autouse=True)
def moe_family_in_the_package(monkeypatch):
    """The family stands where a new family's file would be,
    ``chipbench/families/<name>.py``, without a file written there."""
    monkeypatch.setitem(sys.modules, f"chipbench.families.{F.NAME}", F)


def _run(kind, **kw):
    c = kw.pop("c", None) or F.config(kind)
    return run.run(c, tiny.mix(LOOPS[kind]), seed=kw.pop("seed", 2**31 + 17),
                   seconds=1.5, trace=kw.pop("trace", False),
                   metric_names=kw.pop("metric_names", NAMES),
                   base_arch=F.base_arch(c), **QUIET, **kw)


def test_weights_are_seeded_and_laid_out_for_the_program():
    c = F.config(dtype="bfloat16")
    a = F.make_weights(c, 2**40 + 3, dtype=jnp.bfloat16)
    b = F.make_weights(c, 2**40 + 3, dtype=jnp.bfloat16)
    d = F.make_weights(c, 3, dtype=jnp.bfloat16)
    assert np.array_equal(np.asarray(a["w_up"], np.float32),
                          np.asarray(b["w_up"], np.float32))
    assert not np.array_equal(np.asarray(a["w_up"], np.float32),
                              np.asarray(d["w_up"], np.float32))
    from repro.models import model as MD
    want = jax.eval_shape(lambda: MD.init_params(jax.random.PRNGKey(0),
                                                 F.base_arch(c)))
    got = jax.eval_shape(lambda: F.program_params(a))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(want)] == \
        [(x.shape, x.dtype) for x in jax.tree.leaves(got)]
    assert got["layers"]["moe"]["w_gate"].shape == (2, 4, 64, 64)


def test_reference_matches_program_forward():
    c = F.config()
    from repro.models import model as MD
    w = F.make_weights(c, 7, dtype=jnp.float32)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 40),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = F.forward_logits(w, c, toks)
        got = MD.forward(F.program_params(w), F.base_arch(c),
                         {"tokens": toks[None]}, attn_impl="reference")
    got = got[0] if isinstance(got, tuple) else got
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # the routed experts matter: without them the logits move
    bare = dict(w, w_down=jnp.zeros_like(w["w_down"]))
    assert not np.allclose(F.forward_logits(bare, c, toks), ref, atol=1e-2)


@pytest.mark.parametrize("kind", sorted(LOOPS))
def test_rehearsal_builds_operands_the_programs_accept(kind):
    """The rehearsal's operands, from the family, lowered and compiled
    for the CPU (no v5e is needed to check the closures take them). The
    program picks its layers by the parameter tree it is given, so a
    dense tree would lower a dense program without complaint: the
    operands must be the program's own MoE tree, and the decode program
    must run the routed experts."""
    from repro.models import model as MD
    c = F.config(kind)
    sh = SingleDeviceSharding(jax.devices()[0])
    programs = rehearsal._programs(c, tiny.mix(LOOPS[kind]), sh,
                                   F.base_arch(c))
    assert [p[0] for p in programs] == \
        ["decode", "chunk_paged" if kind == "paged" else "prefill"]
    want = jax.eval_shape(lambda: MD.init_params(jax.random.PRNGKey(0),
                                                 F.base_arch(c)))
    for _, fn, args, _ in programs:
        assert jax.tree.structure(args[0]) == jax.tree.structure(want)
        assert [(x.shape, x.dtype) for x in jax.tree.leaves(args[0])] == \
            [(x.shape, x.dtype) for x in jax.tree.leaves(want)]
        lowered = fn.lower(*args)
        assert "ecd,edf->ecf" in lowered.as_text(debug_info=True)
        assert lowered.compile().memory_analysis() is not None


@pytest.mark.parametrize("kind", sorted(LOOPS))
def test_a_run_serves_correct(kind):
    out = _run(kind)
    assert out["correct"], out["checked"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compiles_in_window"] == 0
    assert set(out["metrics"]) >= {"itl_p95_ms", "setup_s"}


def routing_reversed(eng):
    """Every MoE layer routes each token to the experts it scores
    lowest: the router's weights negated under the warmed programs."""
    moe = eng.params["layers"]["moe"]
    eng.params["layers"]["moe"] = dict(moe, router=-moe["router"])


@pytest.mark.parametrize("fault", [routing_reversed,
                                   test_faults.token_altered],
                         ids=["routing-reversed", "token-altered"])
def test_broken_timed_path_is_not_correct(fault):
    out = _run("contig", fault=fault)
    assert not out["correct"], out["checked"]
    assert out["checked"]["max_logit_gap"]["value"] > \
        out["checked"]["max_logit_gap"]["limit"]


def test_traced_run_reads_the_moe_scope(tmp_path, monkeypatch):
    """A traced run through ``run.run``: the program's own spans from
    the CPU's trace, and, since the CPU keeps no device plane, one decode
    program's operations standing in for it, with the ``moe`` scope on
    the path a program that names its routed layer writes."""
    extract = SP.extract

    def with_device(path):
        events = extract(path)
        lo = next(e[3] for e in events if e[2] == TR.WINDOW_SPAN)
        prefix = "jit(decode)/while/body/closed_call/"
        for name, start, dur, scope in (
                ("fusion.1", 10, 100, "attention/dot_general"),
                ("fusion.2", 110, 300, "mlp/moe/ecd,edf->ecf/dot_general"),
                ("fusion.3", 410, 100, "mlp/nd,de->ne/dot_general")):
            events.append(["/device:TPU:0", "XLA Ops", name, lo + start,
                           float(dur), {"op": prefix + scope}])
        events.append(["/device:TPU:0", "XLA Modules", "jit_decode(1)",
                       lo + 10, 500.0, {}])
        return events
    monkeypatch.setattr(SP, "extract", with_device)
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    (tmp_path / "moe_us_per_decode.py").write_text(MOE_METRIC)
    spec = importlib.util.spec_from_file_location(
        "moe_us_per_decode", tmp_path / "moe_us_per_decode.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    reader = run.metric_reader
    monkeypatch.setattr(run, "metric_reader", lambda name: mod.read if
                        name == "moe_us_per_decode" else reader(name))
    seen = []
    reduce = SP.reduce
    monkeypatch.setattr(SP, "reduce", lambda ev, scopes=(): seen.append(
        reduce(ev, scopes)) or seen[-1])

    out = _run("contig", trace=True,
               metric_names=["moe_us_per_decode", "device_idle_share"])
    assert out["correct"], out["checked"]
    (red,) = seen
    n = red["dispatch"]["decode"]["n"]
    assert n > 0 and red["dispatch"]["decode"]["rows"] >= n
    assert red["device_s"]["decode"] == pytest.approx(
        {"attention": 100e-9, "moe": 300e-9, "mlp": 100e-9})
    assert out["metrics"]["moe_us_per_decode"]["value"] == \
        pytest.approx(0.3 / n)
    assert out["device"]["busy_s"] == pytest.approx(500e-9)


def test_the_file_as_the_program_runs_it_is_taken():
    c = F.config()
    arch = system.arch_config(c, F.base_arch(c))
    assert (arch.n_experts, arch.moe_top_k, arch.d_ff_expert,
            arch.d_ff_first_dense) == (4, 2, 64, 128)


@pytest.mark.parametrize("key,value", [
    ("n_routed_experts", 8), ("experts_held", 2),
    ("num_experts_per_tok", 3), ("moe_intermediate_size", 32),
    ("n_shared_experts", 2), ("first_k_dense_replace", 2),
    ("intermediate_size", 64), ("hidden_size", 128)])
def test_an_expert_key_the_program_does_not_run_is_refused(key, value):
    c = F.config()
    base = F.base_arch(c)
    with pytest.raises(ValueError, match=f"{key}={value} but the program"):
        system.arch_config(dict(c, **{key: value}), base)


@pytest.mark.parametrize("change,match", [
    ({"norm_topk_prob": False}, "norm_topk_prob=False"),
    ({"scoring_func": "sigmoid"}, "routes by softmax"),
    ({"moe_layer_freq": 2}, "routes by softmax"),
    ({"tie_word_embeddings": True}, "tied embeddings")],
    ids=["norm-topk", "sigmoid", "layer-freq", "tied"])
def test_a_router_the_program_does_not_run_is_refused(change, match):
    c = F.config()
    with pytest.raises(ValueError, match=match):
        system.arch_config(dict(c, **change), F.base_arch(c))


def test_a_capacity_that_drops_tokens_is_refused():
    """The registry's factor, 1.25, can drop routed tokens at the smoke
    widths (4 experts, top 2), which the reference never does."""
    c = F.config()
    with pytest.raises(ValueError, match="drops routed tokens"):
        system.arch_config(c, F.base_arch(c, capacity_factor=1.25))
