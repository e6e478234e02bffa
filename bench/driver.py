"""Open-loop driver: submits each request when it is due, steps the engine,
and stamps every token on the host clock after ``Engine.step`` returns (the
step ends by copying the sampled logits to the host, so the tokens exist).

Times are seconds on one clock whose zero is the traffic start. The driver
never reads the engine's own timestamps (``Request.first_token_time``,
``token_times``): those are on whatever clock the engine was handed.

Host spans: the driver wraps the engine's layer calls in
``jax.profiler.TraceAnnotation`` and times them, so a trace shows what the
host was doing in each device gap and ``sched_ms_per_step`` reads the
scheduler's share.
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax

# (object attribute, span name): the engine's layer calls the driver times
SPANS = (
    ("scheduler.next_step", "sched.next_step"),
    ("scheduler.complete_step", "sched.complete_step"),
    ("_apply_prefix_copies", "engine.prefix_copies"),
    ("_apply_swaps", "engine.swaps"),
    ("_run_packed", "engine.run_packed"),
    ("_sync_block_mirror", "engine.block_mirror"),
    ("_issue_prefetch", "engine.prefetch"),
)
SCHED = ("sched.next_step", "sched.complete_step")


@dataclass
class Rec:
    rid: int
    due: float
    prompt_len: int
    max_new: int
    submit: float = 0.0
    tokens: List[float] = field(default_factory=list)
    finish: Optional[float] = None
    admit: Optional[float] = None  # first step that scheduled a chunk of it
    failed: bool = False


@dataclass
class Step:
    t0: float
    t1: float
    q_lens: tuple
    kv_lens: tuple
    sampled: int
    sched_s: float


def _timed(fn, name, acc):
    def call(*a, **k):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                return fn(*a, **k)
            finally:
                acc[name] += time.perf_counter() - t
    return call


def instrument(eng) -> Dict[str, float]:
    """Wrap the engine's layer calls (``SPANS``) in host spans; returns the
    seconds spent in each, accumulated from now on."""
    span_s: Dict[str, float] = defaultdict(float)
    for path, name in SPANS:
        obj, attr = eng, path
        if "." in path:
            head, attr = path.split(".")
            obj = getattr(eng, head)
        setattr(obj, attr, _timed(getattr(obj, attr), name, span_s))
    return span_s


class Driver:
    def __init__(self, eng, source, compiles: List[float]):
        self.eng = eng
        self.source = source
        self.compiles = compiles  # host times of backend compiles (listener)
        self.t0 = time.perf_counter()
        self.recs: Dict[int, Rec] = {}
        self.steps: List[Step] = []
        self.span_s = instrument(eng)
        self.lag: List[float] = []
        self.finished = 0  # requests that have emitted their last token

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _submit(self, now):
        from repro.serving.request import Request

        with jax.profiler.TraceAnnotation("driver.submit"):
            for it in self.source.pop_due(now):
                rid = len(self.recs)
                self.recs[rid] = Rec(rid, it.due, len(it.prompt), it.max_new,
                                     submit=now)
                self.lag.append(now - it.due)
                try:
                    self.eng.submit(Request(rid=rid, prompt=it.prompt,
                                            max_new_tokens=it.max_new,
                                            arrival_time=it.due))
                except ValueError:
                    self.recs[rid].failed = True

    def step(self) -> bool:
        """Submit what is due, run one engine step or wait for the next
        arrival. Returns False when there is nothing left to do."""
        now = self.now()
        self._submit(now)
        sch = self.eng.scheduler
        if not sch.has_work:
            nxt = self.source.next_due()
            if nxt is None:
                return False
            with jax.profiler.TraceAnnotation("driver.idle"):
                time.sleep(max(0.0, min(nxt - self.now(), 0.05)))
            return True
        sched0 = sum(self.span_s[s] for s in SCHED)
        t0 = self.now()
        with jax.profiler.TraceAnnotation("driver.step"):
            plan = self.eng.step(now=t0)
        t1 = self.now()
        if plan is None:
            with jax.profiler.TraceAnnotation("driver.idle"):
                time.sleep(0.001)
            return True
        with jax.profiler.TraceAnnotation("driver.record"):
            self._record(plan, t0, t1, sum(self.span_s[s] for s in SCHED) - sched0)
        return True

    def _record(self, plan, t0, t1, sched_s):
        sch = self.eng.scheduler
        rids = list(plan.decode_rids) + [s.rid for s in plan.prefill_segments]
        for seg in plan.prefill_segments:
            rec = self.recs[seg.rid]
            if rec.admit is None:
                rec.admit = t0
        sampled = 0
        for rid in set(rids):
            req, rec = sch.requests[rid], self.recs[rid]
            new = len(req.output) - len(rec.tokens)
            sampled += max(new, 0)
            rec.tokens.extend([t1] * max(new, 0))
            if req.state.value == "done" and rec.finish is None:
                rec.finish = t1
                self.finished += 1
            elif req.state.value == "cancelled":
                rec.failed = True
        cu = plan.cu_q_lens
        self.steps.append(Step(t0, t1, tuple(b - a for a, b in zip(cu, cu[1:])),
                               tuple(plan.kv_lens), sampled, sched_s))

    def run_until(self, t_end: float) -> None:
        while self.now() < t_end and self.step():
            pass

    def warm_up(self, completions: int, quiet_s: float, max_s: float) -> float:
        """Run the traffic until ``completions`` requests have finished and
        no compile happened in the last ``quiet_s`` seconds, so the window
        opens once the first requests have gone through every stage, at the
        same point of the work however fast the steps are. Gives up waiting
        at ``max_s``. Returns the window's start on the driver clock."""
        shown = 0.0
        while True:
            self.run_until(self.now() + 0.25)
            t = self.now()
            if t - shown >= 10.0:
                shown = t
                print(f"[bench] warm-up t={t:.1f}s steps={len(self.steps)} "
                      f"in slots={len(self.eng.scheduler.active)} "
                      f"waiting={len(self.eng.scheduler.waiting)} "
                      f"finished={self.finished} compiles={len(self.compiles)}", flush=True)
            if t >= max_s:
                return t
            if self.finished < completions:
                continue
            if not any(c > self.t0 + t - quiet_s for c in self.compiles):
                return t
