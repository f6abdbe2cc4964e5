"""The control that the comparison must fail: the float32 reference
computed in fp8 (the precision below the configuration's bf16), at each
position of the served sequences, read by the gap of the token it puts
first. At a tiny size on the CPU the bf16 program stays inside the
limit on every seed and the control falls outside it; on the chip the
same reading is made at each cell's own size (``chipbench/control.py``).
"""
from __future__ import annotations

import pytest

from chipbench import run
from chipbench.tests import tiny

LIMIT = 0.05    # between tiny bf16 program and fp8 control readings
CELLS = {"paged-open": ("paged", "open", None, 4),
         "contig-closed": ("contig", "closed", 128, 2)}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_program_passes_and_fp8_control_fails(cell, seed):
    kind, loop, window, kv = CELLS[cell]
    c = tiny.config(kind=kind, window=window, kv_heads=kv,
                    dtype="bfloat16")
    c["check"].update(max_logit_gap=LIMIT, sample_requests=64)
    out = run.run(c, tiny.mix(loop), seed=seed, seconds=1.5, trace=False,
                  metric_names=[], base_arch=tiny.base_arch(c),
                  control=True, log=lambda *a, **k: None)
    ctl = out["control"]
    got = (out["checked"]["max_logit_gap"]["value"],
           ctl["checked"]["max_logit_gap"]["value"])
    assert out["correct"], got
    assert not ctl["correct"], got
    assert got[1] > LIMIT, got
