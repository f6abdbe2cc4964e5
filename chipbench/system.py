"""The one place the benchmark touches the system under test: it builds
the serving program's model configuration and engine from a
configuration file (one ``ServingEngine``, or a ``ClusterEngine`` of one
worker per chip where the cell asks for a cluster), with the program's
telemetry on in a traced run, and in a traced run watches the engines'
dispatch methods from outside (the live lengths each dispatch worked
on, priced by the configuration's family, ``families.Needed``).

The engine is driven only through its public entry points
(``ServingEngine(params, cfg, EngineConfig(...))`` or
``ClusterEngine(params, cfg, EngineConfig(...), ClusterConfig(...))``,
``submit``, ``step``, ``run``). The watch in a traced run wraps each
serving engine's own mechanism methods (``_admit_one``, ``_run_chunk``,
``_decode_step``, ``_dispatch``) and reads their host state; when a
later program has none of them, the wrap is skipped and the metrics that
need it report nothing.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

from chipbench import arch_check, families

ROOT = Path(__file__).resolve().parents[1]


def import_program():
    """Put the program's sources on the path; fail plainly without
    them."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise RuntimeError(f"the serving program is not in this checkout "
                           f"(no {src / 'repro'})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def arch_config(c: dict, base=None):
    """The program's ``ArchConfig`` for configuration ``c`` (its
    registry entry, or ``base``), checked by the configuration's family
    to run the widths, layers and window the file states: its ``WIDTHS``
    and ``check_arch``, each ``arch_check``'s where the family defines
    none. Raises ``ValueError`` where the program would run something
    else."""
    import_program()
    from repro.configs import registry
    family = families.load(c)
    arch = base or registry.get_config(c["registry_arch"])
    arch_check.check_widths(c, arch, getattr(family, "WIDTHS",
                                             arch_check.WIDTHS))
    getattr(family, "check_arch", arch_check.check_arch)(c, arch)
    return arch.replace(dtype=c["torch_dtype"])


def engine_config(c: dict):
    import_program()
    from repro.serving import EngineConfig
    return EngineConfig(**c["engine"])


def make_engine(params, c: dict, base=None, *, telemetry: bool = False,
                devices=None):
    """The serving engine of configuration ``c``, with the program's
    telemetry on where ``telemetry``. Where ``c`` holds ``cluster``
    (``{"n_prefill", "n_decode"}``, from the cell's file), a
    ``ClusterEngine`` with one worker on each of ``devices``, prefill
    workers first; the first worker serves ``params`` where they lie,
    without a second copy."""
    import_program()
    from repro.serving import ServingEngine
    from repro.serving.telemetry import Telemetry
    tel = Telemetry() if telemetry else None
    arch, ecfg = arch_config(c, base), engine_config(c)
    if not c.get("cluster"):
        return ServingEngine(params, arch, ecfg, telemetry=tel)
    import jax
    from repro.serving import ClusterConfig, ClusterEngine
    k = c["cluster"]
    n = int(k["n_prefill"]) + int(k["n_decode"])
    devices = list(devices or jax.devices())
    if len(devices) != n:
        raise ValueError(f"a cluster of {n} workers takes one chip each, "
                         f"given {len(devices)}")
    eng = ClusterEngine(params, arch, ecfg, ClusterConfig(
        n_prefill=int(k["n_prefill"]), n_decode=int(k["n_decode"]),
        devices=tuple(devices)), telemetry=tel)
    first = eng.prefill_workers[0].params
    if any(a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()
           for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(first))
           if a.devices() == b.devices()):
        raise RuntimeError("the first cluster worker copied the weights "
                           "on the chip that holds them")
    return eng


def serving_engines(eng) -> list:
    """The serving engines that ``eng`` drives: itself, or a cluster's
    workers' (prefill workers first)."""
    workers = getattr(eng, "prefill_workers", None)
    if workers is None:
        return [eng]
    return [w.eng for w in workers + eng.decode_workers]


class Watch:
    """Per-dispatch needed-work records for a traced run, over every
    serving engine that ``eng`` drives.

    ``records`` gets one ``(step, kind, Work)`` per model dispatch,
    priced by ``needed`` (``families.Needed``) from the live lengths and
    the dispatch span's integer arguments; ``step`` is the harness's
    step counter, set before each ``eng.step()``."""

    def __init__(self, eng, needed):
        self.records: list = []
        self.step = 0
        self.needed = needed
        engines = serving_engines(eng)
        self.enabled = all(hasattr(e, m) for e in engines for m in (
            "_admit_one", "_run_chunk", "_decode_step", "_dispatch",
            "prefilling", "slot_pos", "prefills",
            "prefill_chunk_dispatches", "decode_dispatches"))
        if self.enabled:
            for e in engines:
                self._wrap(e)

    def _wrap(self, eng):
        admit_one, run_chunk, decode, dispatch = (
            eng._admit_one, eng._run_chunk, eng._decode_step, eng._dispatch)
        args = {}       # kind -> span arguments of its last dispatch
        chunk = "chunk_" + eng.ecfg.kv_cache
        nd = self.needed

        def _dispatch(kind, fn, params, *a, **span_args):
            args[kind] = dict(span_args)
            return dispatch(kind, fn, params, *a, **span_args)

        def _admit_one(slot, req):
            before = eng.prefills
            out = admit_one(slot, req)
            if eng.prefills > before:
                n = min(len(req.prompt), eng.ecfg.max_seq_len - 1)
                self.records.append((self.step, "prefill",
                                     nd.prefill_work(n, 0, args.get("prefill", {}))))
            return out

        def _run_chunk(slot):
            st = eng.prefilling[slot]
            h = int(st.done)
            n = min(int(eng.ecfg.chunk_tokens), int(st.n_prompt) - h)
            before = eng.prefill_chunk_dispatches
            out = run_chunk(slot)
            if eng.prefill_chunk_dispatches > before:
                self.records.append((self.step, "chunk",
                                     nd.prefill_work(n, h, args.get(chunk, {}))))
            return out

        def _decode_step(live):
            pos = np.asarray(eng.slot_pos)[np.asarray(live, bool)].copy()
            before = eng.decode_dispatches
            out = decode(live)
            if eng.decode_dispatches > before:
                self.records.append((self.step, "decode",
                                     nd.decode_work(pos, args.get("decode", {}))))
            return out

        eng._admit_one, eng._run_chunk, eng._decode_step, eng._dispatch = (
            _admit_one, _run_chunk, _decode_step, _dispatch)
