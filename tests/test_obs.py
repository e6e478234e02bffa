"""Step-level tracing subsystem (repro.obs) + tools/check_trace.py.

Covers the observability tentpole's guarantees:

  * typed metrics registry semantics — kind/unit/percentile collisions
    raise ``MetricCollision``, re-registration is get-or-create, counters
    are monotone, histogram flattening matches the historical key shape;
  * NaN-safe JSON — ``json_safe``/``dump_json`` never emit the non-standard
    ``NaN``/``Infinity`` tokens;
  * trace recording — lifecycle instants derive per-request state spans,
    disabled tracing records nothing (the NOOP singleton);
  * Chrome export — the object form ``ui.perfetto.dev`` loads;
  * the trace-invariant checker — passes on real traces, *fails* on
    corrupted ones (a checker that cannot fail checks nothing);
  * engine/sim schedule-determined sequence identity on a real workload.
"""
from __future__ import annotations

import json
import math
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduce_config
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.obs import (
    NOOP,
    MetricCollision,
    MetricsRegistry,
    TraceRecorder,
    dump_json,
    export_chrome,
    json_safe,
)
from repro.serving.request import Request

REPO = Path(__file__).resolve().parent.parent
CHECKER = REPO / "tools" / "check_trace.py"

SWAP_KNOBS = dict(chunk_size=16, max_decode_batch=3,
                  prefetch_buffer_bytes=0, max_concurrent_prefills=2,
                  kv_capacity_tokens=30, preemption="swap", kv_block_size=4)


def run_checker(*args):
    return subprocess.run([sys.executable, str(CHECKER)]
                          + [str(a) for a in args],
                          capture_output=True, text=True)


def drive(sched: Scheduler, max_steps: int = 500) -> int:
    """Dummy backend: decode rows + finishing prefills emit one token each."""
    step = 0
    while sched.has_work and step < max_steps:
        plan = sched.next_step(now=float(step))
        if plan is None:
            break
        for rid in plan.decode_rids:
            sched.requests[rid].output.append(0)
        for rid in plan.finishing_rids:
            sched.requests[rid].output.append(0)
        sched.complete_step(plan, now=float(step))
        step += 1
    return step


def swap_requests():
    return [Request(rid=i, prompt=[7] * L, max_new_tokens=o)
            for i, (L, o) in enumerate([(17, 6), (23, 5), (12, 7)])]


def traced_sched_run(tmp_path: Path, name: str = "trace.json") -> Path:
    """Drive the scheduler over an over-subscribed swap workload with a
    manual-clock recorder and export the Chrome trace."""
    tr = TraceRecorder("sched-test", manual_clock=True)
    sched = Scheduler(SchedulerConfig(**SWAP_KNOBS),
                      get_config("llama3.1-8b"), tracer=tr)
    for r in swap_requests():
        sched.add_request(r)
    drive(sched)
    path = tmp_path / name
    export_chrome(tr, str(path))
    return path


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_is_monotone():
    reg = MetricsRegistry()
    c = reg.counter("n", "events")
    c.inc(3)
    c.inc()
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registration_is_get_or_create():
    reg = MetricsRegistry()
    a = reg.counter("n", "events", "help once")
    b = reg.counter("n", "events")
    assert a is b
    assert len(reg) == 1


def test_kind_collision_raises():
    reg = MetricsRegistry()
    reg.counter("x", "events")
    with pytest.raises(MetricCollision):
        reg.gauge("x", "events")


def test_unit_collision_raises():
    reg = MetricsRegistry()
    reg.counter("x", "bytes")
    with pytest.raises(MetricCollision):
        reg.counter("x", "tokens")


def test_histogram_percentile_collision_raises():
    reg = MetricsRegistry()
    reg.histogram("lat", "s", percentiles=(50, 99))
    with pytest.raises(MetricCollision):
        reg.histogram("lat", "s", percentiles=(99,))


def test_as_dict_flattens_histograms_and_keeps_types():
    reg = MetricsRegistry()
    reg.counter("completed", "requests").inc(3)
    reg.gauge("rate", "req/s").set(1.5)
    reg.histogram("lat", "s", percentiles=(50, 99)).observe_all([1.0, 2.0, 3.0])
    reg.histogram("empty", "s", percentiles=(50,))
    d = reg.as_dict()
    assert d["completed"] == 3 and isinstance(d["completed"], int)
    assert d["rate"] == 1.5
    assert d["lat_p50"] == 2.0 and "lat" not in d
    assert math.isnan(d["empty_p50"])
    assert set(reg.flat_names()) == set(d)


# ---------------------------------------------------------------------------
# NaN-safe JSON
# ---------------------------------------------------------------------------

def test_json_safe_replaces_nonfinite():
    obj = {"a": float("nan"), "b": [1.0, float("inf")],
           "c": {"d": float("-inf"), "e": 2}}
    safe = json_safe(obj)
    assert safe == {"a": None, "b": [1.0, None], "c": {"d": None, "e": 2}}


def test_dump_json_is_strict_json(tmp_path):
    path = tmp_path / "m.json"
    dump_json(str(path), {"x": float("nan"), "y": 1})

    def reject(tok):
        raise AssertionError(f"non-finite token {tok!r} in output")

    with open(path) as f:
        m = json.load(f, parse_constant=reject)
    assert m == {"x": None, "y": 1}


# ---------------------------------------------------------------------------
# trace recording
# ---------------------------------------------------------------------------

def test_noop_tracer_is_default_and_records_nothing():
    sched = Scheduler(SchedulerConfig(**SWAP_KNOBS),
                      get_config("llama3.1-8b"))
    assert sched.trace is NOOP
    assert NOOP.enabled is False
    for r in swap_requests():
        sched.add_request(r)
    drive(sched)
    assert not hasattr(NOOP, "events")


def test_lifecycle_spans_derived_from_instants():
    tr = TraceRecorder("t", manual_clock=True)
    tr.set_time(0.0)
    tr.request_event(0, "arrival", ts=0.0, sched_key=False)
    tr.request_event(0, "admit", ts=1.0)
    tr.request_event(0, "first_token", ts=2.5)
    tr.request_event(0, "finish", ts=5.0)
    tr.close()
    spans = [(e.name, e.ts, e.dur) for e in tr.events
             if e.ph == "X" and e.lane == "request"]
    assert spans == [("queued", 0.0, 1.0), ("prefill", 1.0, 1.5),
                     ("decode", 2.5, 2.5)]


def test_close_finishes_open_spans():
    tr = TraceRecorder("t", manual_clock=True)
    tr.request_event(1, "arrival", ts=0.0, sched_key=False)
    tr.span("compute", "c", 0.0, 4.0)
    tr.close()
    (span,) = [e for e in tr.events if e.ph == "X" and e.lane == "request"]
    assert span.name == "queued" and span.ts == 0.0 and span.dur == 4.0


def test_arrival_excluded_from_sched_sequence():
    tr = TraceRecorder("t", manual_clock=True)
    tr.request_event(0, "arrival", ts=0.0, sched_key=False)
    tr.request_event(0, "admit", ts=1.0, slot=0)
    assert len(tr.sched_sequence()) == 1
    assert tr.sched_sequence()[0][0] == "admit"


def test_manual_clock_is_monotone():
    tr = TraceRecorder("t", manual_clock=True)
    tr.set_time(3.0)
    tr.set_time(1.0)  # never runs backwards
    assert tr.now() == 3.0


# ---------------------------------------------------------------------------
# Chrome export
# ---------------------------------------------------------------------------

def test_chrome_export_shape(tmp_path):
    path = traced_sched_run(tmp_path)
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    phases = {e["ph"] for e in events}
    assert phases <= {"X", "i", "C", "M"}
    for e in events:
        assert "name" in e and "pid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # request rows live in their own process; sched keys are JSON strings
    assert any(e["pid"] == 2 for e in events)
    assert any("sched" in e.get("args", {}) for e in events)


# ---------------------------------------------------------------------------
# check_trace.py
# ---------------------------------------------------------------------------

def test_checker_passes_on_real_trace(tmp_path):
    path = traced_sched_run(tmp_path)
    r = run_checker(path)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_checker_compare_identical_runs(tmp_path):
    a = traced_sched_run(tmp_path, "a.json")
    b = traced_sched_run(tmp_path, "b.json")
    r = run_checker(a, "--compare", b)
    assert r.returncode == 0, r.stderr
    assert "sched sequences identical" in r.stdout


def _write(tmp_path: Path, name: str, events) -> Path:
    path = tmp_path / name
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path


def test_checker_rejects_lane_overlap(tmp_path):
    path = _write(tmp_path, "bad.json", [
        {"name": "a", "ph": "X", "pid": 1, "tid": 3, "ts": 0.0, "dur": 10.0,
         "cat": "compute", "args": {}},
        {"name": "b", "ph": "X", "pid": 1, "tid": 3, "ts": 5.0, "dur": 10.0,
         "cat": "compute", "args": {}},
    ])
    r = run_checker(path)
    assert r.returncode == 1
    assert "lane overlap" in r.stderr


def test_checker_rejects_consume_before_land(tmp_path):
    path = _write(tmp_path, "bad.json", [
        {"name": "swap_in:issued", "ph": "i", "pid": 1, "tid": 9, "ts": 0.0,
         "s": "t", "cat": "prefetch_queue",
         "args": {"tid": 5, "state": "issued", "nbytes": 64.0}},
        {"name": "swap_in:consumed", "ph": "i", "pid": 1, "tid": 9, "ts": 1.0,
         "s": "t", "cat": "prefetch_queue",
         "args": {"tid": 5, "state": "consumed", "nbytes": 64.0,
                  "late_bytes": 0.0, "sync": False}},
    ])
    r = run_checker(path)
    assert r.returncode == 1
    assert "un-landed" in r.stderr


def test_checker_rejects_dropped_request(tmp_path):
    path = _write(tmp_path, "bad.json", [
        {"name": "admit", "ph": "i", "pid": 2, "tid": 1, "ts": 0.0, "s": "t",
         "cat": "request", "args": {"rid": 0}},
    ])
    r = run_checker(path)
    assert r.returncode == 1
    assert "never reached a terminal" in r.stderr


def test_checker_rejects_nan_tokens(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text('{"traceEvents": [{"name": "a", "ph": "X", "pid": 1, '
                    '"tid": 1, "ts": NaN, "dur": 1.0, "args": {}}]}')
    r = run_checker(path)
    assert r.returncode == 2
    assert "NaN" in r.stderr or "non-finite" in r.stderr


def test_checker_detects_sequence_divergence(tmp_path):
    a = traced_sched_run(tmp_path, "a.json")
    # same workload minus one request: schedules must diverge
    tr = TraceRecorder("sched-test", manual_clock=True)
    sched = Scheduler(SchedulerConfig(**SWAP_KNOBS),
                      get_config("llama3.1-8b"), tracer=tr)
    for r in swap_requests()[:2]:
        sched.add_request(r)
    drive(sched)
    b = tmp_path / "b.json"
    export_chrome(tr, str(b))
    r = run_checker(a, "--compare", b)
    assert r.returncode == 1
    assert "sched-sequence" in r.stderr


# ---------------------------------------------------------------------------
# engine vs sim: identical schedule-determined event sequences
# ---------------------------------------------------------------------------

def test_engine_and_sim_emit_identical_sched_sequences(tmp_path):
    from repro.models import build_model
    from repro.serving.engine import Engine
    from repro.sim.hardware import TPUV6E
    from repro.sim.service import simulate_service

    cfg = reduce_config(get_config("llama3.1-8b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    eng_tr = TraceRecorder("engine")
    eng = Engine(model, params, SchedulerConfig(async_prefetch=True,
                                                **SWAP_KNOBS),
                 max_len=64, tracer=eng_tr)
    for r in swap_requests():
        eng.submit(r)
    eng.run(max_steps=500)
    # stamp the run-total attribution instant (as launch.serve and the
    # benches do) so the exported trace carries its conservation anchor
    eng.scheduler.ledger.record_totals(eng_tr, eng.attribution_aggregates())

    sim_tr = TraceRecorder("sim", manual_clock=True)
    simulate_service(
        TPUV6E, cfg, workload=None, qps=1.0, mode="packed",
        chunk=SWAP_KNOBS["chunk_size"],
        max_decode_batch=SWAP_KNOBS["max_decode_batch"],
        max_concurrent_prefills=SWAP_KNOBS["max_concurrent_prefills"],
        kv_capacity_tokens=SWAP_KNOBS["kv_capacity_tokens"],
        preemption="swap", kv_block_size=SWAP_KNOBS["kv_block_size"],
        async_prefetch=True, requests=swap_requests(), tracer=sim_tr,
    )

    seq_e, seq_s = eng_tr.sched_sequence(), sim_tr.sched_sequence()
    assert seq_e, "engine recorded no schedule-determined events"
    assert seq_e == seq_s

    # and the full checker agrees end-to-end on the exported files
    pe = tmp_path / "engine.json"
    ps = tmp_path / "sim.json"
    export_chrome(eng_tr, str(pe))
    export_chrome(sim_tr, str(ps))
    r = run_checker(pe, "--compare", ps)
    assert r.returncode == 0, r.stderr

    # both backends recorded real per-lane busy spans, and the sim's step
    # phases never overlap inside a lane (checker-verified above)
    assert any(e.ph == "X" and e.lane == "step" for e in eng_tr.events)
    assert any(e.ph == "X" and e.lane == "compute" for e in sim_tr.events)


def test_chrome_trace_is_loadable_object_form(tmp_path):
    """The exporter's contract with ui.perfetto.dev: object form, µs
    timestamps, thread metadata present."""
    path = traced_sched_run(tmp_path)
    trace = json.loads(path.read_text())
    assert set(trace) >= {"traceEvents", "displayTimeUnit"}
    names = [e for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert names, "no thread_name metadata — Perfetto rows would be unnamed"


# ---------------------------------------------------------------------------
# host spans (obs.trace.span) and the engine's step phases
# ---------------------------------------------------------------------------

STEP_PHASES = ("sched.next_step", "engine.prefix_copies", "engine.swaps",
               "engine.pack", "engine.dispatch", "engine.fetch",
               "engine.sample", "engine.prefetch", "sched.complete_step")


class _Refuses:
    """A disabled recorder that fails on any recording call."""

    enabled = False

    def now(self):
        raise AssertionError("a disabled recorder's clock was read")

    def span(self, *a, **k):
        raise AssertionError("a disabled recorder recorded a span")


def test_span_nests_and_accumulates():
    import time

    from repro.obs.trace import span

    tr = TraceRecorder("t")
    acc = ({}, {})
    for i in range(3):
        with span("outer", "step", tr, acc, step_num=i, step=i) as args:
            with span("inner", "host", tr, acc, step=i):
                time.sleep(0.002)
            args["tokens"] = 7
    secs, calls = acc
    assert calls == {"outer": 3, "inner": 3}
    assert secs["outer"] >= secs["inner"] >= 0.006
    outer = [e for e in tr.events if e.name == "outer"]
    inner = [e for e in tr.events if e.name == "inner"]
    assert [e.step for e in outer] == [0, 1, 2]
    assert all(e.lane == "step" and e.args == {"tokens": 7} for e in outer)
    for o, n in zip(outer, inner):  # the inner span lies inside the outer
        assert o.ts <= n.ts and n.ts + n.dur <= o.ts + o.dur + 1e-9


def test_span_counts_without_recording_when_disabled():
    from repro.obs.trace import span

    acc = ({}, {})
    with span("a", "host", _Refuses(), acc):
        pass
    with span("a", "host", NOOP, acc, step=1):
        pass
    assert acc[1] == {"a": 2} and acc[0]["a"] >= 0.0


def test_span_counts_when_the_body_raises():
    from repro.obs.trace import span

    acc = ({}, {})
    with pytest.raises(ValueError):
        with span("a", "host", NOOP, acc):
            raise ValueError
    assert acc[1] == {"a": 1}


def test_chrome_export_carries_both_clock_origins(tmp_path):
    import time

    t, ns = time.perf_counter(), time.time_ns()
    tr = TraceRecorder("engine")
    path = tmp_path / "t.json"
    export_chrome(tr, str(path))
    other = json.loads(path.read_text())["otherData"]
    assert other["origin_perf_counter_s"] == tr.origin[0] >= t
    assert other["origin_unix_ns"] == tr.origin[1] >= ns


@pytest.fixture(scope="module")
def engine_run(tmp_path_factory):
    """The swap workload through a tiny paged engine, recorded by a
    TraceRecorder and by the JAX profiler (on the CPU)."""
    from jax.profiler import ProfileData

    from repro.models import build_model
    from repro.obs import MetricsRegistry
    from repro.serving.engine import Engine

    cfg = reduce_config(get_config("llama3.1-8b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tr = TraceRecorder("engine")
    eng = Engine(model, params, SchedulerConfig(async_prefetch=True,
                                                **SWAP_KNOBS),
                 max_len=64, tracer=tr)
    for r in swap_requests():
        eng.submit(r)
    eng.step()  # compile outside the profiled stretch
    out = tmp_path_factory.mktemp("engine_run")
    jax.profiler.start_trace(str(out / "prof"))
    eng.run(max_steps=500)
    jax.profiler.stop_trace()
    eng.scheduler.ledger.record_totals(tr, eng.attribution_aggregates())
    export_chrome(tr, str(out / "trace.json"))
    reg = MetricsRegistry()
    eng.register_metrics(reg)
    pb = next((out / "prof").rglob("*.xplane.pb"))
    host = [(e.start_ns, e.end_ns, e.name)
            for p in ProfileData.from_file(str(pb)).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith(("engine.", "sched."))]
    return eng, tr, out / "trace.json", reg, host


def test_engine_phases_tile_the_step(engine_run):
    eng, _, _, _, _ = engine_run
    assert eng.steps_run > 10
    assert set(eng.span_s) == {"engine.step", *STEP_PHASES}
    assert eng.span_n["engine.step"] >= eng.steps_run
    assert eng.span_n["sched.next_step"] == eng.span_n["engine.step"]
    inner = sum(eng.span_s[p] for p in STEP_PHASES)
    assert 0.9 * eng.span_s["engine.step"] <= inner <= eng.span_s["engine.step"]


def test_engine_trace_export_passes_the_checker(engine_run):
    _, tr, path, _, _ = engine_run
    r = run_checker(path)
    assert r.returncode == 0, r.stderr
    names = {e.name for e in tr.events if e.ph == "X" and e.lane != "request"}
    assert names == {"engine.step", *STEP_PHASES}


def test_engine_span_counters_are_registered(engine_run):
    eng, _, _, reg, _ = engine_run
    flat = reg.as_dict()
    for name in ("engine.step", *STEP_PHASES):
        assert flat[f"span_s.{name}"] == eng.span_s[name]
        assert flat[f"span_n.{name}"] == eng.span_n[name]
    assert reg.get("span_s.engine.step").unit == "s"
    assert reg.get("span_n.engine.step").unit == "calls"


def test_engine_phases_nest_in_the_profiler_step(engine_run):
    eng, _, _, _, host = engine_run
    steps = sorted((a, b) for a, b, n in host if n == "engine.step")
    assert len(steps) >= eng.steps_run - 1
    seen = set()
    for a, b, name in host:
        if name == "engine.step":
            continue
        # every phase lies inside one engine.step of the profiler's trace
        assert any(s <= a and b <= e for s, e in steps), name
        seen.add(name)
    assert seen == set(STEP_PHASES)


def test_step_op_scopes_name_every_op_of_the_step():
    import re

    from repro.core.packed_step import STEP_SCOPES
    from repro.models import build_model
    from repro.serving.engine import Engine

    cfg = reduce_config(get_config("qwen3-32b"), periods=3)  # a scanned stack
    model = build_model(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 SchedulerConfig(chunk_size=16, max_decode_batch=3,
                                 kv_block_size=4), max_len=64)
    scopes = eng.step_op_scopes(8, 8, 16)
    # a dense model has no routed experts, so no MoE scope
    assert set(scopes.values()) == set(STEP_SCOPES) - {"moe_route", "moe_experts"}
    text = eng._packed.lower(
        *jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                      (eng.params, eng.cache)),
        *[jax.ShapeDtypeStruct(s, jnp.int32) for s in
          [(eng.bucket,)] * 3 + [(eng.n_slots + 1, 8), (9,), (8,), (8,)]],
        16).compile().as_text()
    assert text.startswith("HloModule jit_paged_step")
    op = re.compile(r'^\s*(?:ROOT\s+)?%(\S+) = .*? ([a-z\-]+)\(.*'
                    r'op_name="jit\(paged_step\)/([^"]*)"')
    pool_ops = 0
    for line in text.splitlines():
        m = op.match(line)
        if not m or m.group(2) in ("parameter", "constant", "bitcast"):
            continue
        name, kind, path = m.groups()
        # the scan's own machinery (the loop, its counter, the slicing of
        # its stacked weights) is all that may go unnamed
        if re.fullmatch(r"while(/(body|cond)/[a-z_]+)?", path):
            continue
        assert name in scopes, (name, path)
        if "closed_call/kv_pool" in path:
            # the stacked pool is written in place: no layer is sliced out
            # of it or written back
            assert kind not in ("dynamic-slice", "dynamic-update-slice"), name
            if kind == "scatter":
                assert scopes[name] == "kv_pool"
                pool_ops += 1
    assert pool_ops >= 2  # the step's K and V pages written into the stack
