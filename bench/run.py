"""The benchmark's one command:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json`` names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); ``bench/cells/<cell>.json`` holds the cell's
warm-up floors and its correctness limit; each metric is read by
``bench/metrics/<metric>.py``; peaks come from ``bench/peaks.json`` by device
kind. A run: build the engine (bf16 weights drawn on the device from the
seed), compile every step shape the traffic reaches, start the traffic, warm
up until steady, measure ``--seconds``, read the device's peak memory, free
the engine, compare a sample of the served tokens (finished requests and
those still in flight) with the float32 reference, and print one JSON line.
``--trace 1`` profiles a few seconds in the middle of the window and reports
the per-layer metrics instead of the end-to-end ones. ``--control 1`` puts
the reference computed in fp8 in the program's place for the comparison: the
tokens it picks first are judged instead of the served ones, and the run
reads ``correct`` false (how a limit's upper reading is taken).

A reader gets what it reads from the run record (``Run``): the engine's
span totals and the counters it registers (``Engine.register_metrics``),
taken over the window; in traced runs the trace's busy time, the device
seconds of every kernel a per-layer reader names in its ``KERNEL``, and the
device seconds by the packed step's named scopes. So a new per-layer metric
is one new reader file.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result. JAX's compilation cache lives in ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_S = 5.0  # profiled seconds, centred in the window
QUIET_S = 5.0  # warm-up ends after this long without a compile
STRETCH_S = 10.0  # output tokens/s is logged by stretches of this length


def info(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


@dataclass
class Run:
    """What one run leaves for the metric readers. ``span_s`` / ``span_n``
    (the engine's span seconds and calls) and ``counters`` (the flat values
    of what ``Engine.register_metrics`` registers) are their changes over
    the window, ``counters_at_close`` the values at its close (gauges).
    Traced runs only: ``trace`` (``trace_reduce.reduce``) and ``scope_s``
    (``trace_scopes.scope_seconds``)."""
    cfg: dict
    peaks: dict
    window: tuple
    setup_s: float
    recs: dict
    window_steps: list
    traced_steps: list = field(default_factory=list)
    trace: dict = None
    compiles_rel: list = field(default_factory=list)
    span_s: dict = field(default_factory=dict)
    span_n: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)
    counters_at_close: dict = field(default_factory=dict)
    scope_s: dict = None


def reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def reader_kernels(names) -> tuple:
    """The kernels whose device time the readers ``names`` read: each
    reader's ``KERNEL``, a name or a tuple of names (matched as a part of
    an operation's name)."""
    out = []
    for name in names:
        k = getattr(reader(name), "KERNEL", ())
        out += [k] if isinstance(k, str) else list(k)
    return tuple(dict.fromkeys(out))


def engine_counters(eng):
    """(span seconds, span calls, registered counters) of the engine, read
    between two steps."""
    from repro.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    eng.register_metrics(reg)
    return dict(eng.span_s), dict(eng.span_n), reg.as_dict()


def change(at_open: dict, at_close: dict) -> dict:
    return {k: v - at_open.get(k, 0) for k, v in at_close.items()}


def load_cell(name: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    from bench import traffic

    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    e2e = [m["name"] for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    layer = [m["name"] for m in bench["per_layer"] if name in m.get("workloads", [name])]
    cell_data = json.loads((ROOT / "bench" / "cells" / f"{name}.json").read_text())
    return cell, cfg, traffic.load(cell["traffic"]), cell_data, metrics, e2e, layer


def use_compile_cache(jax) -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, require_tpu: bool = True, fault=None, mc=None, loaded=None):
    """Run one cell; returns the exit code. The keyword arguments serve the
    harness's own tests on the CPU: ``require_tpu=False`` skips the look for
    a chip, ``loaded`` replaces what ``load_cell`` reads, ``mc`` the
    program's model config, and ``fault(engine)`` breaks the timed path."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the tokens the fp8 reference picks first in "
                         "place of the served ones (a limit's upper reading)")
    args = ap.parse_args(argv)
    cell, cfg, mix, cell_data, metric_specs, e2e, layer = (
        loaded if loaded is not None else load_cell(args.workload))

    import jax
    import numpy as np

    use_compile_cache(jax)
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}
    info(f"device platform={dev.platform} kind={dev.device_kind} count={len(devs)}")
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell["chips"]):
        print(f"[bench] needs {cell['chips']} TPU chip(s); JAX sees "
              f"{len(devs)} {dev.platform} device(s)", file=sys.stderr)
        return 3
    peaks_all = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"]
    peaks = peaks_all.get(dev.device_kind)
    if peaks is None and require_tpu:
        print(f"[bench] no peaks for device kind {dev.device_kind!r} in "
              "bench/peaks.json", file=sys.stderr)
        return 3

    compiles = []  # host perf_counter times of backend compiles
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: compiles.append(time.perf_counter())
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    from bench import engine_build, traffic
    from bench.driver import Driver

    eng, pages, basis = engine_build.build(cfg, args.seed, mc=mc)
    nb, sb, qb = engine_build.buckets(eng, cfg, cell_data)
    t = time.perf_counter()
    n_shapes = engine_build.prewarm(eng, nb, sb, qb)
    info(f"engine pool={pages} pages ({basis}) slots={eng.n_slots} rows={eng.bucket} "
         f"prewarmed {n_shapes} step shapes nb={nb} sb={sb} qb={qb} in "
         f"{time.perf_counter() - t:.1f}s, compiles so far {len(compiles)}")
    if fault is not None:
        fault(eng)

    source = traffic.make(mix, seed=args.seed, vocab=cfg["vocab_size"],
                          max_len=cfg["engine"]["max_len"])
    drv = Driver(eng, source, compiles)
    w0 = drv.warm_up(int(mix["warmup"]["completions"]), QUIET_S,
                     float(mix["warmup"]["max_s"]))
    setup_s = drv.t0 + w0 - T_START
    t = time.perf_counter()
    at_open = engine_counters(eng)
    read_s = [time.perf_counter() - t]
    w1 = w0 + args.seconds
    n0 = len(drv.steps)
    trace_red, traced = None, (None, None)
    tmp = None
    if args.trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        mid = w0 + max(0.0, (args.seconds - TRACE_S) / 2)
        drv.run_until(mid)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.block_until_ready(eng.cache)
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.traced"):
            tr0 = drv.now()
            drv.run_until(min(w1, mid + TRACE_S))
            jax.block_until_ready(eng.cache)
            tr1 = drv.now()
        jax.profiler.stop_trace()
        traced = (tr0, tr1)
    drv.run_until(w1)
    t = time.perf_counter()
    at_close = engine_counters(eng)
    read_s.append(time.perf_counter() - t)
    info(f"engine spans {len(at_close[0])} and counters {len(at_close[2])} read "
         f"at the window's open and close in {1e6 * read_s[0]:.0f} and "
         f"{1e6 * read_s[1]:.0f} us")
    window_steps = drv.steps[n0:]
    lag = np.asarray(drv.lag or [0.0])
    info(f"window [{w0:.3f}, {w1:.3f}] s on the driver clock, steps {len(window_steps)}, "
         f"requests submitted {len(drv.recs)}, compiles in set-up "
         f"{sum(1 for c in compiles if c < drv.t0 + w0)}")
    info(f"generator lag s: p50={np.percentile(lag, 50):.4f} "
         f"p99={np.percentile(lag, 99):.4f} max={lag.max():.4f}")
    if window_steps:
        dur = np.asarray([s.t1 - s.t0 for s in window_steps])
        shapes = sorted({engine_build.shape_of(eng, s.q_lens, s.kv_lens)
                         for s in window_steps})
        info(f"step s: p50={np.percentile(dur, 50):.4f} p95={np.percentile(dur, 95):.4f} "
             f"mean={dur.mean():.4f}; rows/step {np.mean([sum(s.q_lens) for s in window_steps]):.1f}; "
             f"(nb, sb, qb) in window {shapes}; all prewarmed: "
             f"{all(x[0] in nb and x[1] in sb and x[2] in qb for x in shapes)}")
    stamps = np.sort([t for r in drv.recs.values() for t in r.tokens])
    edges = np.arange(0.0, w1 + STRETCH_S, STRETCH_S)
    info(f"output tokens/s by {STRETCH_S:.0f} s stretch from the traffic start "
         f"(window from {w0:.1f} s): "
         f"{(np.histogram(stamps, edges)[0] / STRETCH_S).round(2).tolist()}; "
         f"finished before the window {sum(1 for r in drv.recs.values() if r.finish is not None and r.finish < w0)}")
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    device["memory_peak_bytes"] = peak
    pool_b = sum(a.nbytes for a in jax.tree.leaves(eng.cache))
    used = eng.scheduler.mem.allocator.peak_used_blocks
    idle_b = int(pool_b * (pages - used) / (pages + 1))  # pool + scratch page
    info(f"pool pages used at most {used} of {pages}; peak bytes {peak}, of which "
         f"pool pages never filled {idle_b}, net {peak - idle_b}")

    scope_s = None
    if tmp is not None:
        from bench import trace_reduce, trace_scopes

        # after the window: the op-to-scope map of each step shape traced
        # (compiled from shapes; the step is in the compile cache)
        t = time.perf_counter()
        traced_shapes = sorted({engine_build.shape_of(eng, s.q_lens, s.kv_lens)
                                for s in drv.steps
                                if s.t1 > traced[0] and s.t0 < traced[1]})
        op_scopes = trace_scopes.merge_op_scopes(
            eng.step_op_scopes(*x) for x in traced_shapes)
        info(f"op-to-scope maps of the traced shapes {traced_shapes}: {len(op_scopes)} ops "
             f"in {time.perf_counter() - t:.1f}s, compiles so far {len(compiles)}")
        t = time.perf_counter()
        ev = trace_scopes.load(next(Path(tmp).rglob("*.xplane.pb")))
        shutil.rmtree(tmp, ignore_errors=True)
        trace_red = trace_reduce.reduce(ev, kernels=reader_kernels(layer))
        scope_s = trace_scopes.scope_seconds(  # the step's compiled module
            ev, {"jit_" + eng._packed.__name__: op_scopes})
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        info(f"trace reduced in {time.perf_counter() - t:.1f}s: window "
             f"{trace_red['window_s']:.4f}s busy {trace_red['busy_s']:.4f}s "
             f"kernels {trace_red['kernel_s']}")
        info(f"device s by scope: { {k: round(v, 4) for k, v in scope_s.items()} }")
        info(f"idle s by host span: "
             f"{ {k: round(v, 4) for k, v in trace_scopes.idle_by_span(ev).items()} }")

    run = Run(cfg=cfg, peaks=peaks or {}, window=(w0, w1), setup_s=setup_s,
              recs=drv.recs, window_steps=window_steps,
              traced_steps=[s for s in window_steps
                            if traced[0] is not None and s.t0 >= traced[0]
                            and s.t1 <= traced[1]],
              trace=trace_red,
              compiles_rel=[c - drv.t0 for c in compiles],
              span_s=change(at_open[0], at_close[0]),
              span_n=change(at_open[1], at_close[1]),
              counters=change(at_open[2], at_close[2]),
              counters_at_close=at_close[2], scope_s=scope_s)
    # the requests the window worked on: due in it, or served or admitted in
    # it (a backlog's requests are all due before the window opens)
    inside = lambda t: t is not None and w0 <= t < w1
    worked = [r for r in drv.recs.values()
              if inside(r.due) or inside(r.admit) or any(map(inside, r.tokens))]
    attempted = len(worked)
    failed = sum(1 for r in worked if r.failed)
    names = layer if args.trace else e2e
    metrics = {}
    for name in names:
        v = reader(name).read(run)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": metric_specs[name]["unit"]}
    for name in (e2e if args.trace else layer):  # the other kind, for the log
        info(f"{name}={reader(name).read(run)}")

    sch = eng.scheduler
    served = [(r.rid, list(sch.requests[r.rid].prompt), list(sch.requests[r.rid].output))
              for r in drv.recs.values() if r.tokens and not r.failed]
    info(f"requests worked on in window {attempted}, failed {failed}, finished in "
         f"window {sum(1 for r in drv.recs.values() if r.finish is not None and w0 <= r.finish < w1)}, "
         f"with served tokens {len(served)}")
    del eng, sch, drv
    gc.collect()
    info(f"device bytes still held after freeing the engine: "
         f"{sum(a.nbytes for a in jax.live_arrays())}")

    from bench import correct

    ref = engine_build.reference(cfg)
    seqs = correct.sample(served, args.seed, cell_data["check_tokens"],
                          cell_data["check_requests"])
    g, secs = correct.gaps(ref, cfg, args.seed, seqs, control=bool(args.control))
    gap = float(g.max()) if g.size else None  # nothing compared: not correct
    limit = float(cell_data["logit_gap_limit"])
    info(f"reference over {len(seqs)} requests, {g.size} "
         f"{'fp8-control' if args.control else 'served'} tokens, "
         f"{sum(len(s[1]) + len(s[2]) for s in seqs)} positions in {secs:.1f}s; "
         f"gap p50={np.percentile(g, 50) if g.size else float('nan'):.4f}")
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "tokens_checked": {"value": int(g.size), "limit": 1}}
    ok = bool(gap is not None and gap <= limit and failed == 0)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace_red is not None:
        result["breakdown"] = {"device_ops": trace_red["device_ops"],
                               "idle_gaps": trace_red["idle_gaps"]}
    result["checks"] = checks
    print(f"check logit_gap={gap!r} limit={limit!r}", file=sys.stderr)
    print(f"check tokens_checked={g.size} limit=1 (at least)", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
