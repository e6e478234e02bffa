"""Scope and span reductions of a profiler trace: device time by the named
scope of the module's operations (the innermost operation wins, so the
scopes sum to busy time), and idle time split over the host spans it
overlaps."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace_reduce as T
from bench import trace_scopes as S
from bench.metrics import engine_host_ms_per_step, kv_pool_share

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


def events():
    # window 0..100 ns, one device: the step module runs [0, 60), another
    # module [70, 90)
    ops = {"/device:TPU:0": [(0, 60, "while.1"), (5, 20, "fusion.1"),
                             (20, 40, "copy.2"), (40, 50, "fusion.3"),
                             (70, 90, "fusion.1")]}
    mods = {"/device:TPU:0": [(0, 60, "jit_paged_step"), (70, 90, "jit_gather")]}
    spans = [(0, 100, "driver.step"), (55, 80, "sched.next_step"),
             (58, 62, "engine.pack")]
    return S.ScopedEvents(window=(0, 100), device_ops=ops, host_spans=spans,
                          modules=mods)


def test_scope_seconds_innermost_op_and_module():
    got = S.scope_seconds(events(), {"jit_paged_step": {"fusion.1": "qkv",
                                                        "copy.2": "kv_pool"}})
    ns = {k: round(v * 1e9) for k, v in got.items()}
    # the loop's own time only where no body op runs: [0,5) and [50,60)
    assert ns == {"qkv": 15, "kv_pool": 20, "unscoped": 25, "jit_gather": 20}
    assert sum(ns.values()) == round(T.reduce(events())["busy_s"] * 1e9)


def test_idle_by_span_splits_a_gap_by_overlap():
    got = {k: round(v * 1e9) for k, v in S.idle_by_span(events()).items()}
    # idle [60,70) and [90,100): [60,62) under engine.pack, [62,70) under
    # sched.next_step, [90,100) under driver.step
    assert got == {"driver.step": 10, "sched.next_step": 8, "engine.pack": 2}
    ev = events()
    ev.host_spans = [(65, 95, "engine.fetch")]
    got = {k: round(v * 1e9) for k, v in S.idle_by_span(ev).items()}
    assert got == {"engine.fetch": 10, "no host span": 10}


def test_merge_op_scopes_marks_disagreement():
    merged = S.merge_op_scopes([{"a": "qkv", "b": "mlp"},
                                {"a": "qkv", "b": "kv_pool", "c": "embed"}])
    assert merged == {"a": "qkv", "b": "ambiguous", "c": "embed"}


def test_module_name():
    assert S.module_name("jit_paged_step(2242102358537937652)") == "jit_paged_step"
    assert S.module_name("jit_gather") == "jit_gather"


def test_recorded_chip_trace_by_span():
    # three 20 ms host spans sched.next_step with the device idle
    ev = S.load(SMALL)
    idle = S.idle_by_span(ev)
    assert 0.058 < idle["sched.next_step"] < 0.065
    assert idle.get("no host span", 0.0) < 0.01
    red = T.reduce(ev)
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"],
                                               abs=1e-9)


def test_recorded_chip_trace_by_scope():
    ev = S.load(SMALL)
    assert {m for _, _, m in ev.modules["/device:TPU:0"]} == {
        "jit__lambda", "jit_mixed_paged_attention"}
    got = S.scope_seconds(ev, {"jit__lambda": {"fusion": "mlp"}})
    assert set(got) == {"mlp", "unscoped", "jit_mixed_paged_attention"}
    assert got["mlp"] > got["unscoped"]
    assert sum(got.values()) == pytest.approx(T.reduce(ev)["busy_s"], abs=1e-9)


def test_readers_without_counters_or_trace_read_none():
    bare = SimpleNamespace(trace=None)
    assert engine_host_ms_per_step.read(bare) is None
    assert kv_pool_share.read(bare) is None
    traced = SimpleNamespace(trace={"busy_s": 1.0}, scope_s=None,
                             span_s={}, span_n={})
    assert engine_host_ms_per_step.read(traced) is None
    assert kv_pool_share.read(traced) is None


def test_engine_host_ms_per_step_leaves_out_the_fetch():
    run = SimpleNamespace(span_s={"engine.step": 2.0, "engine.fetch": 1.5},
                          span_n={"engine.step": 100})
    assert engine_host_ms_per_step.read(run) == pytest.approx(5.0)


def test_kv_pool_share_refuses_a_poorly_named_step():
    tr = {"busy_s": 2.0}
    run = SimpleNamespace(trace=tr, scope_s={"kv_pool": 0.5, "attention": 1.0,
                                             "unscoped": 0.1, "jit_gather": 0.4})
    assert kv_pool_share.read(run) == pytest.approx(25.0)
    run.scope_s["ambiguous"] = 0.1  # 0.2 of 1.7 s of the step unnamed
    assert kv_pool_share.read(run) is None
