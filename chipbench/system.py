"""The one place the benchmark touches the system under test: it builds
the serving program's model configuration and engine from a
configuration file, and, in a traced run, watches the engine's dispatch
methods from outside (host spans for the trace, and the live lengths
each dispatch worked on, for ``work.py``).

The engine is driven only through its public entry points
(``ServingEngine(params, cfg, EngineConfig(...))``, ``submit``,
``step``). The watch in a traced run wraps the engine's own mechanism
methods (``_admit_one``, ``_run_chunk``, ``_decode_step``) and reads
their host state; when a later program has none of them, the wrap is
skipped and the metrics that need it report nothing.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

from chipbench import work as W

ROOT = Path(__file__).resolve().parents[1]

# configuration key -> the program's ArchConfig field; each must agree
_WIDTHS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta"}


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
    registry entry, or ``base``), checked to run the widths, layers and
    window the file states; only the RMSNorm epsilon, which the program
    hard-codes, is not compared. Raises ``ValueError`` where the program
    would run something else."""
    import_program()
    from repro.configs import registry
    arch = base or registry.get_config(c["registry_arch"])
    for key, field in _WIDTHS.items():
        if float(getattr(arch, field)) != float(c[key]):
            raise ValueError(f"{c['registry_arch']}: {key}={c[key]} but "
                             f"the program runs {field}="
                             f"{getattr(arch, field)}")
    dh = int(c.get("head_dim") or c["hidden_size"] //
             c["num_attention_heads"])
    if arch.d_head != dh or arch.tie_embeddings != c["tie_word_embeddings"]:
        raise ValueError("head size or tied embeddings differ from the "
                         "program")
    if (arch.activation != "swiglu" or arch.norm != "rmsnorm"
            or arch.qkv_bias or c.get("attention_bias")):
        raise ValueError("the dense family expects SwiGLU, RMSNorm and no "
                         "attention bias")
    win, cap = c.get("sliding_window"), int(c["engine"]["max_seq_len"])
    if arch.sliding_window != win:
        # a window no position of this engine can reach masks nothing:
        # the program may run the layer without one
        if not (arch.sliding_window is None and win is not None
                and win >= cap - 1):
            raise ValueError(f"sliding_window={win} but the program runs "
                             f"{arch.sliding_window}")
    return arch.replace(dtype=c["torch_dtype"])


def engine_config(c: dict):
    import_program()
    from repro.serving import EngineConfig
    return EngineConfig(**c["engine"])


def make_engine(params, c: dict, base=None):
    import_program()
    from repro.serving import ServingEngine
    return ServingEngine(params, arch_config(c, base), engine_config(c))


class Watch:
    """Host spans and per-dispatch work records for a traced run.

    ``records`` gets one ``(step, kind, Work)`` per model dispatch;
    ``step`` is the harness's step counter, set before each
    ``engine.step()``."""

    def __init__(self, eng, c: dict):
        import jax
        self.records: list = []
        self.step = 0
        self.shapes = W.Shapes.from_config(c)
        self.enabled = all(hasattr(eng, m) for m in (
            "_admit_one", "_run_chunk", "_decode_step", "prefilling",
            "slot_pos", "prefills", "prefill_chunk_dispatches",
            "decode_dispatches"))
        if not self.enabled:
            return
        ann = jax.profiler.TraceAnnotation
        sch = eng.scheduler
        for obj, name in ((sch, "admit"), (sch, "retire")):
            fn = getattr(obj, name)

            def spanned(*a, _fn=fn, _n=f"engine.{name}", **k):
                with ann(_n):
                    return _fn(*a, **k)
            setattr(obj, name, spanned)

        admit_one, run_chunk, decode = (eng._admit_one, eng._run_chunk,
                                        eng._decode_step)

        def _admit_one(slot, req):
            before = eng.prefills
            with ann("engine.prefill"):
                out = admit_one(slot, req)
            if eng.prefills > before:
                n = min(len(req.prompt), eng.ecfg.max_seq_len - 1)
                self.records.append((self.step, "prefill",
                                     W.prefill(self.shapes, n)))
            return out

        def _run_chunk(slot):
            st = eng.prefilling[slot]
            h = int(st.done)
            n = min(int(eng.ecfg.chunk_tokens), int(st.n_prompt) - h)
            before = eng.prefill_chunk_dispatches
            with ann("engine.chunk"):
                out = run_chunk(slot)
            if eng.prefill_chunk_dispatches > before:
                self.records.append((self.step, "chunk",
                                     W.prefill(self.shapes, n, h)))
            return out

        def _decode_step(live):
            pos = np.asarray(eng.slot_pos)[np.asarray(live, bool)].copy()
            before = eng.decode_dispatches
            with ann("engine.decode"):
                out = decode(live)
            if eng.decode_dispatches > before:
                self.records.append((self.step, "decode",
                                     W.decode(self.shapes, pos)))
            return out

        eng._admit_one, eng._run_chunk, eng._decode_step = (
            _admit_one, _run_chunk, _decode_step)
