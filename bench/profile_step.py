"""One cell's ``--trace 1`` run of ``bench/run.py``, read besides through
what the program marks inside itself:

    python3 bench/profile_step.py --workload <cell> --seed <n> --seconds <s> [--out <dir>]

- ``engine_host_ms_per_step``: the engine's own span counters
  (``Engine.span_s`` / ``span_n``) taken when the window opens and closes;
- ``kv_pool_share`` and device seconds by named scope: the op-to-scope map of
  every step shape the traced stretch ran (``Engine.step_op_scopes``, taken
  after the window, before the engine is freed) over the trace
  (``trace_scopes.scope_seconds``), and the ten top operations with their
  scope;
- idle device time by host span, split by overlap
  (``trace_scopes.idle_by_span``);
- the cost of tracing: the engine's ten step spans timed on this host with
  the profiler off, and output tokens/s and step time in the traced stretch
  against the rest of the window.

``bench/run.py`` prints its own lines and result first; this script then
prints ``[profile]`` lines and one JSON line, also written to
``<dir>/profile_<cell>_<seed>.json`` with ``--out``. ``run.py`` does not hand these
fields to its metric readers; this script gives the two readers
(``bench/metrics/engine_host_ms_per_step.py``, ``kv_pool_share.py``) what
they read. A program without the spans or the map gives ``null`` for them.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R  # noqa: E402  (starts run.py's set-up clock)

PHASES = ("engine.step", "sched.next_step", "engine.prefix_copies",
          "engine.swaps", "engine.pack", "engine.dispatch", "engine.fetch",
          "engine.sample", "engine.prefetch", "sched.complete_step")


def info(msg: str) -> None:
    print(f"[profile] {msg}", flush=True)


def span_cost_us(n: int = 2000):
    """Host microseconds per step that the engine's step spans cost with the
    profiler off: one outer span and nine phases, as ``Engine.step`` runs
    them. None where the program has no ``span`` helper."""
    try:
        from repro.obs.trace import NOOP, span
    except ImportError:
        return None
    acc = ({}, {})
    t = time.perf_counter()
    for i in range(n):
        with span(PHASES[0], "step", NOOP, acc, step_num=i, step=i):
            for name in PHASES[1:]:
                with span(name, "host", NOOP, acc, step=i):
                    pass
    return 1e6 * (time.perf_counter() - t) / n


def main(argv, **run_kw):
    """``run_kw`` goes to ``bench.run.main`` (its CPU test hooks)."""
    import jax

    from bench import driver, engine_build, trace_reduce, trace_scopes

    args = dict(zip(argv[::2], argv[1::2]))
    cell, seed = args["--workload"], int(args["--seed"])
    seconds = float(args["--seconds"])
    st = {"cost_us": span_cost_us(), "maps": {}, "map_s": 0.0}

    def counters(eng):
        return (dict(getattr(eng, "span_s", {})), dict(getattr(eng, "span_n", {})))

    warm_up = driver.Driver.warm_up

    def opened(drv, *a, **k):  # the window opens when the warm-up returns
        w0 = warm_up(drv, *a, **k)
        st.update(drv=drv, w0=w0, open=counters(drv.eng))
        return w0

    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def started(*a, **k):
        start(*a, **k)
        st["tr0"] = st["drv"].now()

    def stopped():
        st["tr1"] = st["drv"].now()
        stop()

    load = trace_reduce.load

    def loaded(path):  # after the window, before the engine is freed
        trace_reduce.load = load
        drv = st.pop("drv")  # hold nothing that keeps the engine alive
        eng = drv.eng
        st.update(steps=drv.steps, recs=drv.recs)
        st["close"] = counters(eng)
        traced = [s for s in drv.steps if s.t1 > st["tr0"] and s.t0 < st["tr1"]]
        shapes = sorted({engine_build.shape_of(eng, s.q_lens, s.kv_lens)
                         for s in traced})
        st["shapes"] = shapes
        if hasattr(eng, "step_op_scopes"):
            t = time.perf_counter()
            st["maps"] = {shape: eng.step_op_scopes(*shape) for shape in shapes}
            st["map_s"] = time.perf_counter() - t
            st["module"] = "jit_" + eng._packed.__name__
        st["events"] = trace_scopes.load(path)
        return st["events"]

    driver.Driver.warm_up = opened
    jax.profiler.start_trace, jax.profiler.stop_trace = started, stopped
    trace_reduce.load = loaded
    rc = R.main(["--workload", cell, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", "1"], **run_kw)
    if rc or "events" not in st:
        return rc or 1

    steps, recs, ev = st["steps"], st["recs"], st["events"]
    w0, w1 = st["w0"], st["w0"] + seconds
    delta = [{k: c[k] - o.get(k, 0) for k in c}
             for o, c in zip(st["open"], st["close"])]
    red = trace_reduce.reduce(ev, kernels=R.KERNELS)
    maps = st["maps"]
    scope_s, top = None, []
    if maps:
        merged = trace_scopes.merge_op_scopes(maps.values())
        scope_s = trace_scopes.scope_seconds(ev, {st["module"]: merged})
        top = [[n, s, merged.get(n, trace_scopes.UNSCOPED)]
               for n, s in red["device_ops"]]
    run = SimpleNamespace(span_s=delta[0], span_n=delta[1], scope_s=scope_s,
                          trace=red)
    from bench.metrics import engine_host_ms_per_step, kv_pool_share

    tr0, tr1 = st["tr0"], st["tr1"]
    win = [s for s in steps if w0 <= s.t0 and s.t1 <= w1]
    inside = [s for s in win if s.t0 >= tr0 and s.t1 <= tr1]
    outside = [s for s in win if s.t1 <= tr0 or s.t0 >= tr1]
    stamps = [t for r in recs.values() for t in r.tokens if w0 <= t < w1]
    n_in = sum(1 for t in stamps if tr0 <= t < tr1)

    def mean_step(steps):
        return sum(s.t1 - s.t0 for s in steps) / len(steps) if steps else None

    out = {
        "workload": cell, "seed": seed,
        "engine_host_ms_per_step": engine_host_ms_per_step.read(run),
        "kv_pool_share": kv_pool_share.read(run),
        "window_steps": len(win),
        "span_s": delta[0], "span_n": delta[1],
        "scope_s": scope_s, "busy_s": red["busy_s"], "window_s": red["window_s"],
        "idle_by_span": trace_scopes.idle_by_span(ev),
        "idle_gaps_midpoint": red["idle_gaps"],
        "top_ops": top,
        "traced_shapes": st.get("shapes"), "op_scopes_s": st["map_s"],
        "span_cost_us_per_step": st["cost_us"],
        "traced": {"seconds": tr1 - tr0, "tokens_per_s": n_in / (tr1 - tr0),
                   "mean_step_s": mean_step(inside), "steps": len(inside)},
        "untraced": {"seconds": seconds - (tr1 - tr0),
                     "tokens_per_s": (len(stamps) - n_in) / (seconds - (tr1 - tr0)),
                     "mean_step_s": mean_step(outside), "steps": len(outside)},
    }
    for k in ("engine_host_ms_per_step", "kv_pool_share", "scope_s",
              "idle_by_span", "top_ops", "traced", "untraced",
              "span_cost_us_per_step", "op_scopes_s"):
        info(f"{k}={out[k]}")
    if "--out" in args:
        dest = Path(args["--out"]) / f"profile_{cell}_{seed}.json"
        dest.parent.mkdir(parents=True, exist_ok=True)
        dest.write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
