"""The one traffic generator: a traffic mix is a data file of parameters
(``chipbench/traffic/<mix>.json``), and this module turns it and a seed
into a plan of requests.

Every seed gets the same work in another order. Sizes and gaps are the
quantiles of their distributions at ``(i + 0.5) / k`` — a stratified
sample — repeated in blocks of ``k``, each block in its own seeded
order. An open loop's blocks are exactly one measured window long and
one of them is the window, so the window holds the same requests for
every seed; a closed loop's blocks are ``STRATA`` requests long. The
spread between seeds then comes from ordering alone.

Keys of a mix:

- ``loop``: ``"open"`` (requests due on a schedule, exponential gaps at
  ``rate_rps``, a fixed number in each window) or ``"closed"`` (``clients`` clients, each sending its
  next request ``think_s`` after its previous one finished).
- ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max", "quantum"}``, or ``{"dist": "fixed", "value"}``.
  Lengths are clipped to ``[min, max]`` and rounded up to a multiple of
  ``quantum`` (a program that compiles one prefill per prompt length
  gets a bounded set of shapes).
- ``residual_start`` (closed loop): each client's first request asks
  for the residual of a length-biased output draw, so the batch opens
  in its steady mix instead of all requests ending together.
- ``bursts`` (open loop, optional): ``{"every_s", "size"}`` adds
  ``size`` requests due together every ``every_s`` seconds.
- ``sessions`` (optional): ``{"groups", "prefix": <length spec>}``
  gives request ``i`` the shared prefix of group ``i % groups`` in
  front of its own prompt (prefix tokens count in the prompt length).
- ``ramp_s``, ``drain_cap_s``: seconds before the window, and the most
  the run waits after it for requests due in it.
- ``trace_s`` (optional, default 10): how much of the window's end a
  traced run profiles; a sparse mix profiles its whole window so that
  every dispatch kind it drives falls in the stretch.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
STRATA = 32         # block of a closed loop's and of bursts' lengths
MIN_STEP_S = 1e-3   # no engine step is shorter: bounds a closed loop's plan
_N = NormalDist()


def load_mix(name: str, root: Path = TRAFFIC_DIR) -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def _quantile(spec: dict, u: np.ndarray, biased: bool = False):
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(u.shape, float(spec["value"]))
    elif dist == "lognormal":
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        if biased:        # length-biased lognormal: mu + sigma^2
            mu += sigma * sigma
        x = np.exp(mu + sigma * np.array([_N.inv_cdf(float(v)) for v in u]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return x


def _shape(spec: dict, x: np.ndarray) -> np.ndarray:
    lo, hi = int(spec.get("min", 1)), int(spec.get("max", 1 << 30))
    q = int(spec.get("quantum", 1))
    x = np.clip(np.ceil(x), lo, hi)
    x = np.ceil(x / q) * q
    return np.minimum(x, hi // q * q).astype(np.int64)


def _strata(n: int, k: int, rng) -> np.ndarray:
    """``n`` values of ``u`` in (0, 1): blocks of the ``k`` midpoints
    ``(i + 0.5) / k``, each block in a seeded order."""
    base = (np.arange(k) + 0.5) / k
    blocks = [rng.permutation(base) for _ in range(-(-n // k))]
    return np.concatenate(blocks)[:n]


def lengths(spec: dict, n: int, rng, k: int) -> np.ndarray:
    return _shape(spec, _quantile(spec, _strata(n, k, rng)))


def _open_arrivals(mix, rng, ramp, seconds, drain):
    """Open-loop due times and lengths in blocks of exactly ``seconds``,
    one of which is the measured window ``[ramp, ramp + seconds)``. A
    block holds ``k = round(rate * seconds)`` arrivals whose gaps are the
    stratified exponential quantiles, scaled to fill the block, and
    lengths stratified over the same ``k``: the window holds the same
    requests for every seed, in another order."""
    rate = float(mix["rate_rps"])
    k = max(1, round(rate * seconds))
    due, p_len, o_len = [], [], []
    for blk in range(-math.ceil(ramp / seconds),
                     math.ceil(drain / seconds) + 1):
        gaps = -np.log1p(-_strata(k, k, rng))
        gaps *= seconds / gaps.sum()
        due.append(ramp + blk * seconds
                   + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
        p_len.append(lengths(mix["prompt"], k, rng, k))
        o_len.append(lengths(mix["output"], k, rng, k))
    due, p_len, o_len = (np.concatenate(x) for x in (due, p_len, o_len))
    keep = due >= 0
    return due[keep], p_len[keep], o_len[keep]


@dataclass
class Plan:
    """Requests in the order they are sent. ``due`` holds each request's
    due time in seconds after the start of traffic (open loop); a closed
    loop's first ``clients`` requests are due at 0 and each later one
    when a client's previous request ends."""
    loop: str
    prompts: list
    n_out: np.ndarray
    due: np.ndarray | None = None
    clients: int = 0
    think_s: float = 0.0


def make_plan(mix: dict, seed: int, seconds: float, vocab: int,
              max_positions: int) -> Plan:
    """The seeded plan for one run that measures for ``seconds``.
    ``max_positions`` bounds prompt + output (the cache capacity)."""
    rng = np.random.default_rng([int(seed), 0x6c6f6164])
    k = STRATA
    ramp, drain = float(mix["ramp_s"]), float(mix["drain_cap_s"])
    if mix["loop"] == "open":
        due, p_len, o_len = _open_arrivals(mix, rng, ramp, seconds, drain)
        b = mix.get("bursts")
        if b:
            times = np.arange(float(b["every_s"]), ramp + seconds + drain,
                              float(b["every_s"]))
            extra = np.repeat(times, int(b["size"]))
            due = np.concatenate([due, extra])
            p_len = np.concatenate([p_len, lengths(mix["prompt"], len(extra),
                                                   rng, k)])
            o_len = np.concatenate([o_len, lengths(mix["output"], len(extra),
                                                   rng, k)])
            order = np.argsort(due, kind="stable")
            due, p_len, o_len = due[order], p_len[order], o_len[order]
        n = len(due)
        clients, think = 0, 0.0
    elif mix["loop"] == "closed":
        clients = int(mix["clients"])
        think = float(mix.get("think_s", 0.0))
        horizon = ramp + seconds + drain
        # a request takes at least its shortest output in steps: the
        # most a client can finish in the horizon
        out = mix["output"]
        shortest = int(out.get("min", out.get("value", 1)))
        per_client = horizon / (MIN_STEP_S * shortest)
        n = clients * (math.ceil(per_client) + 1)
        due = None
        p_len = lengths(mix["prompt"], n, rng, k)
        o_len = lengths(mix["output"], n, rng, k)
        if mix.get("residual_start"):
            full = _shape(mix["output"], _quantile(
                mix["output"], _strata(clients, clients, rng), biased=True))
            frac = _strata(clients, clients, rng)
            o_len[:clients] = np.maximum(2, np.ceil(full * frac)).astype(
                np.int64)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    prefixes = None
    s = mix.get("sessions")
    if s:
        groups = int(s["groups"])
        plen = lengths(s["prefix"], groups, rng, groups)
        prefixes = [rng.integers(0, vocab, int(x), dtype=np.int32)
                    for x in plen]
    prompts = []
    for i in range(n):
        room = max_positions - int(o_len[i])
        length = int(min(p_len[i], room))
        tail = rng.integers(0, vocab, length, dtype=np.int32)
        if prefixes is not None:
            pre = prefixes[i % len(prefixes)][:length]
            tail[:len(pre)] = pre
        prompts.append(tail)
    return Plan(loop=mix["loop"], prompts=prompts, n_out=o_len, due=due,
                clients=clients, think_s=think)
