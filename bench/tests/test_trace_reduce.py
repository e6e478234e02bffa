"""Trace reduction: busy time is the union of device op intervals inside the
traced window, kernel time sums that kernel's events, and each idle gap is
labelled by the innermost host span in flight; the harness hands the
reduction every kernel its per-layer readers name."""
import json
import sys
import types
from pathlib import Path

import pytest

from bench import run
from bench import trace_reduce as T
from bench.tests.tiny import ROOT

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


def events():
    # window 0..100 ns; two device planes
    ops = {
        "/device:TPU:0": [(10, 40, "while.2"), (10, 30, "fusion.1"),
                          (20, 40, "mixed_paged_attention.3"),
                          (60, 70, "mixed_paged_attention.3"), (95, 120, "copy")],
        "/device:TPU:1": [(0, 50, "while.2"), (0, 50, "fusion.1")],
    }
    spans = [(0, 100, "driver.step"), (35, 65, "sched.next_step"),
             (80, 100, "engine.run_packed")]
    return T.Events(window=(0, 100), device_ops=ops, host_spans=spans)


def test_busy_kernel_and_gaps():
    r = T.reduce(events(), kernels=("mixed_paged_attention",))
    assert r["devices"] == 2 and r["window_s"] == 100e-9
    # device 0 busy [10,40) + [60,70) + [95,100) = 45; device 1 [0,50) = 50
    assert abs(r["busy_s"] - 47.5e-9) < 1e-18
    assert abs(r["kernel_s"]["mixed_paged_attention"] - 15e-9) < 1e-18
    gaps = dict(r["idle_gaps"])  # device 0: [0,10) [40,60) [70,95)
    assert abs(gaps["driver.step"] - 10e-9) < 1e-18
    assert abs(gaps["sched.next_step"] - 20e-9) < 1e-18
    assert abs(gaps["engine.run_packed"] - 25e-9) < 1e-18
    # a loop's interval holds its body's ops: busy, but never ranked
    assert r["device_ops"][0][0] == "fusion.1"
    assert "while.2" not in dict(r["device_ops"])


def test_op_name_and_kind():
    assert T.op_name("%fusion.12 = bf16[8]{0} fusion(%a)") == "fusion.12"
    assert T.op_name("%mixed_paged_attention.11 = bf16[16,2] custom-call()") == \
        "mixed_paged_attention.11"
    assert T.op_name("copy-start") == "copy-start"
    assert T.op_kind("while.55") == "while" and T.op_kind("copy.86.remat2") == "copy"
    assert T.op_kind("copy-start") == "copy-start"


def test_merge():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_recorded_chip_trace():
    # bench/tests/data/small.xplane.pb: record_trace.py on one v5e; three
    # steps, each a 20 ms host span with the device idle, then a matmul and
    # the mixed paged attention kernel
    r = T.reduce(T.load(SMALL), kernels=("mixed_paged_attention",))
    assert r["devices"] == 1 and 0.06 < r["window_s"] < 0.2
    assert 0 < r["busy_s"] < 0.1 * r["window_s"]
    assert 0 < r["kernel_s"]["mixed_paged_attention"] < r["busy_s"]
    names = [n for n, _ in r["device_ops"]]
    assert any(n.startswith("mixed_paged_attention") for n in names)
    label, idle = r["idle_gaps"][0]
    assert label == "sched.next_step" and idle > 0.06


def test_kernel_time_by_reader_on_recorded_chip_trace():
    # the benchmark's per-layer readers name the mixed kernel alone (the
    # roofline reader's KERNEL), and its device seconds on the recorded
    # trace are those the harness's former fixed kernel list gave
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    kernels = run.reader_kernels([m["name"] for m in bench["per_layer"]])
    assert kernels == ("mixed_paged_attention",)
    r = T.reduce(T.load(SMALL), kernels=kernels)
    assert r["kernel_s"] == {"mixed_paged_attention": pytest.approx(48159e-9, abs=1e-15)}


def test_a_new_reader_names_its_own_kernels(monkeypatch):
    stub = types.ModuleType("bench.metrics.expert_matmul_roofline")
    stub.KERNEL = ("expert_matmul", "router")
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    kernels = run.reader_kernels(["device_idle_share", "mixed_paged_attention_roofline",
                                  "expert_matmul_roofline"])
    assert kernels == ("mixed_paged_attention", "expert_matmul", "router")
    ev = events()
    ev.device_ops["/device:TPU:0"] += [(72, 88, "expert_matmul.7"),
                                       (88, 94, "router_fusion.2")]
    r = T.reduce(ev, kernels=kernels)
    # summed over the two devices, over two
    assert r["kernel_s"] == pytest.approx({"mixed_paged_attention": 15e-9,
                                           "expert_matmul": 8e-9, "router": 3e-9},
                                          abs=1e-18)
