"""Dry-run integration: one small cell lowers+compiles on both production
meshes in a subprocess (512 forced host devices), and the collective parser
handles tuple all-reduces and loop scaling."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


def _run(out, arch, shape, extra=(), timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = str(out)
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--out", out, "--tag", "citest", *extra]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout[-2000:]}\nstderr:\n{r.stderr[-2000:]}"
    mesh = "pod2x16x16" if "--multi-pod" in extra else "pod16x16"
    path = os.path.join(out, f"{arch}__{shape}__{mesh}__citest.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.timeout(500)
def test_dryrun_single_pod_decode(tmp_path):
    rec = _run(tmp_path, "qwen2-1.5b", "decode_32k")
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 256
    assert rec["cost"].get("flops", 0) > 0
    assert rec["memory"]["temp_size_in_bytes"] > 0
    assert rec["collectives"]["total"] >= rec["collectives"]["total_raw"] > 0


@pytest.mark.timeout(500)
def test_dryrun_multi_pod_train(tmp_path):
    rec = _run(tmp_path, "qwen2-1.5b", "train_4k", extra=("--multi-pod",))
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 512
    # loop-trip scaling must amplify in-body collectives
    assert rec["collectives"]["total"] > rec["collectives"]["total_raw"]


def test_dryrun_skip_cell(tmp_path):
    rec = _run(tmp_path, "qwen2-1.5b", "long_500k", timeout=120)
    assert rec["status"] == "skip"
    assert "full-attention" in rec["reason"]


def test_collective_parser_tuple_and_depth():
    from repro.launch.dryrun import parse_collective_bytes

    hlo = """
ENTRY %main (p0: f32[4]) -> f32[4] {
  %ar = (f32[8]{0}, bf16[16]{0}) all-reduce(%a, %b), replica_groups={{0,1,2,3}}
  %w = s32[] while(%t), body=%region_1.1, condition=%c
}

%region_1.1 (arg: (s32[])) -> (s32[]) {
  %ag = f32[32]{0} all-gather(%x), replica_groups=[4,2]<=[8], dimensions={0}
}
"""
    out = parse_collective_bytes(hlo, trips_by_depth=(10.0, 10.0, 10.0))
    assert out["all-reduce"] == 8 * 4 + 16 * 2  # tuple summed, depth 0
    assert out["all-gather"] == (32 * 4 / 2) * 10  # operand=result/groupsize, x10 trips
