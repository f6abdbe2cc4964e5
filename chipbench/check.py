"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the
requests the engine finished, drawn from the seed and always holding
the one with the most output tokens, goes through the family's float32
reference: the prompt and the served tokens as one teacher-forced
sequence. At every position that produced a served token, the gap is
how far the reference's logit of that token lies below the reference's
best logit. Greedy decoding serves the argmax of the program's own
logits, so the gap stays within the program's rounding; a wrong cache
row, position, mask or sampled token opens it far wider. The widest gap
over the sample is compared with the configuration's
``check.max_logit_gap``.

Every finished request must also carry exactly the tokens it asked for.
"""
from __future__ import annotations

import numpy as np


def sample(done: list, seed: int, n: int) -> list:
    """``n`` finished requests: the longest output first, then a seeded
    draw from the rest."""
    if not done:
        return []
    order = sorted(done, key=lambda s: (-len(s.req.output), s.index))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0x636865636b])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def reference_gaps(weights, c: dict, picked: list, length: int,
                   family, control: bool = False):
    """Per request: the widest reference gap of its served tokens, and
    with ``control`` the widest gap of the tokens an fp8 computation of
    the reference puts first on the same sequences."""
    import jax.numpy as jnp
    fn = family.gap_fn(c, control=control)
    out = []
    for s in picked:
        seq, tgt, (a, b) = family.np_tokens(
            np.asarray(s.req.prompt), list(s.req.output), length)
        gap, cgap = fn(weights, jnp.asarray(seq), jnp.asarray(tgt))
        row = {"index": s.index, "prompt": s.n_prompt,
               "served": len(s.req.output),
               "gap": float(np.max(np.asarray(gap)[a:b]))}
        if control:
            row["control_gap"] = float(np.max(np.asarray(cgap)[a:b]))
        out.append(row)
    return out


def verdict(rows: list, short: int, limit: float) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    widest = max((r["gap"] for r in rows), default=None)
    checked = {"max_logit_gap": {"value": widest, "limit": limit},
               "short_outputs": {"value": short, "limit": 0}}
    ok = widest is not None and widest <= limit and short == 0
    return ok, checked
