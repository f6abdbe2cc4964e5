"""The serving loop: sends each request when it is due, steps the
engine, and stamps every output token on the host clock as ``step()``
returns it. Latencies are taken from these stamps, from the client's
side, and each request is timed from when it was *due*, so a stall
that delays later submissions counts against them.
"""
from __future__ import annotations

import heapq
import time
from contextlib import nullcontext
from dataclasses import dataclass, field


@dataclass
class Sent:
    """One request as the client sees it."""
    index: int                  # position in the plan
    due: float                  # host clock
    submit: float
    n_prompt: int
    n_out: int
    req: object                 # the engine's request
    stamps: list = field(default_factory=list)


@dataclass
class Step:
    t0: float
    t1: float
    decoded: int                # tokens from the decode dispatch


@dataclass
class Record:
    """Everything a metric reads about one run."""
    sent: list
    steps: list
    w0: float                   # window, host clock
    w1: float
    setup_s: float = 0.0
    trace: dict | None = None   # trace.reduce() of the traced stretch
    spans: dict | None = None   # spans.reduce() of the traced stretch
    stretch_work: dict | None = None   # kind -> work.Work in the stretch
    needed: object = None       # families.Needed of the configuration
    peaks: dict | None = None
    chips: int = 1

    def due_in_window(self):
        return [s for s in self.sent if self.w0 <= s.due < self.w1]

    def steps_in_window(self):
        return [s for s in self.steps if self.w0 <= s.t1 < self.w1]


class Tracer:
    """Start and stop of the profiler around the last ``seconds`` of the
    window (traced runs only); steps ``first_step .. last_step - 1`` ran
    inside it."""

    def __init__(self, directory, seconds: float):
        self.dir, self.seconds = str(directory), seconds
        self.t0 = self.t1 = None
        self.first_step = self.last_step = None
        self._ann = None

    def start(self, now: float, step: int):
        import jax
        jax.profiler.start_trace(self.dir)
        from chipbench.trace import WINDOW_SPAN
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self.t0, self.first_step = time.perf_counter(), step

    def stop(self, step: int):
        import jax
        self.t1, self.last_step = time.perf_counter(), step
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @property
    def running(self):
        return self.t0 is not None and self.t1 is None


def serve(eng, plan, *, ramp_s: float, seconds: float, drain_cap_s: float,
          tracer: Tracer | None = None, watch=None,
          clock=time.perf_counter, sleep=time.sleep) -> Record:
    """Drive ``eng`` with ``plan`` (see ``loadgen.Plan``): a ramp of
    ``ramp_s`` seconds, the window of ``seconds``, then a drain until
    every request due in the window has its first token (at most
    ``drain_cap_s``)."""
    span = None
    if tracer is not None:
        import jax
        span = jax.profiler.TraceAnnotation
    ann = span or (lambda _n: nullcontext())
    origin = clock()
    w0, w1 = origin + ramp_s, origin + ramp_s + seconds
    due = []                        # heap of (due time, plan index)
    if plan.loop == "open":
        for i, d in enumerate(plan.due):
            due.append((origin + float(d), i))
        next_i = len(plan.due)
    else:
        due = [(origin, i) for i in range(plan.clients)]
        next_i = plan.clients
    heapq.heapify(due)
    sent, active, steps = [], [], []
    n_plan = len(plan.prompts)
    k = 0
    while True:
        now = clock()
        if tracer is not None:
            # the stretch ends with the window, so the seconds that
            # stopping the profiler takes fall after it
            if tracer.t0 is None and now >= max(w0, w1 - tracer.seconds):
                tracer.start(now, k)
            elif tracer.running and now >= w1:
                tracer.stop(k)
        with ann("chipbench.submit"):
            while due and due[0][0] <= now:
                d, i = heapq.heappop(due)
                prompt = plan.prompts[i]
                req = eng.submit(prompt, max_new_tokens=int(plan.n_out[i]))
                s = Sent(i, d, clock(), len(prompt), int(plan.n_out[i]),
                         req)
                sent.append(s)
                active.append(s)
        if active:
            if watch is not None:
                watch.step = k
            t0 = clock()
            with ann("chipbench.step"):
                eng.step()
            t1 = clock()
            k += 1
            with ann("chipbench.stamp"):
                dec = 0
                still = []
                for s in active:
                    new = len(s.req.output) - len(s.stamps)
                    if new:
                        # a request's first token comes from its prefill
                        dec += new - (0 if s.stamps else 1)
                        s.stamps.extend([t1] * new)
                    if len(s.stamps) >= s.n_out:
                        if plan.loop == "closed":
                            if next_i >= n_plan:
                                raise RuntimeError("traffic plan exhausted")
                            heapq.heappush(due, (t1 + plan.think_s, next_i))
                            next_i += 1
                    else:
                        still.append(s)
                active = still
            steps.append(Step(t0, t1, dec))
        else:
            with ann("chipbench.wait"):
                wait = (due[0][0] - now) if due else 0.001
                sleep(min(max(wait, 0.0), 0.002))
        now = clock()
        if now >= w1 and (tracer is None or not tracer.running):
            pending = [s for s in sent if w0 <= s.due < w1 and not s.stamps]
            unsent = due and due[0][0] < w1
            if (not pending and not unsent) or now >= w1 + drain_cap_s:
                break
        if not due and not active and now >= w1:
            break
    if tracer is not None and tracer.running:
        tracer.stop(k)
    return Record(sent=sent, steps=steps, w0=w0, w1=w1)
