"""The harness end to end on the CPU at tiny widths, without the look for a
chip: a sound run is correct, and each fault a served cell can have, planted
under the timed path, turns ``correct`` false.

- state: the step returns the KV pool it was given (nothing is written);
- token: every fifth sampled token is replaced where it is produced;
- half: half of the sampled rows take the other half's logits (their own
  computation left out).

A per-layer reader that is a new file gets the engine's spans and counters
over the window, and in a traced run the device time of the kernel it
names.
"""
import json
import sys
import types

import numpy as np
import pytest

from bench import run, trace_reduce
from bench.tests import tiny

ARGS = ["--workload", "tiny.cell", "--seed", str(2**33 + 5), "--seconds", "3",
        "--trace", "0"]


def state_unchanged(eng):
    import jax
    import jax.numpy as jnp

    step = eng._packed

    def broken(p, cache, *a):
        kept = jax.tree.map(jnp.copy, cache)  # the step donates its input
        logits, _ = step(p, cache, *a)
        return logits, kept
    eng._packed = broken


def token_altered(eng):
    sample = eng._sample_rows

    def broken(rows):
        out = np.array(sample(rows))
        out[::5] = (out[::5] + 1) % rows.shape[-1]
        return out
    eng._sample_rows = broken


def half_left_out(eng):
    sample = eng._sample_rows

    def broken(rows):
        rows = np.array(rows)
        h = len(rows) // 2
        if h:
            rows[h:2 * h] = rows[:h]
        return sample(rows)
    eng._sample_rows = broken


def result(capsys, fault=None):
    rc = run.main(ARGS, require_tpu=False, fault=fault, mc=tiny.model_config(),
                  loaded=tiny.loaded())
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_sound_run_is_correct(capsys):
    rc, res = result(capsys)
    assert rc == 0 and res["correct"], res
    assert list(res)[-1] == "checks" and res["checks"]["tokens_checked"]["value"] > 0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"setup_s", "itl_p95_s", "output_tokens_per_s"} <= set(res["metrics"])


@pytest.mark.parametrize("fault", [state_unchanged, token_altered, half_left_out])
def test_fault_is_not_correct(capsys, fault):
    rc, res = result(capsys, fault)
    assert rc == 0 and not res["correct"], res


def test_no_chip_no_result(capsys):
    rc = run.main(ARGS, mc=tiny.model_config(), loaded=tiny.loaded())
    assert rc == 3 and capsys.readouterr().out.strip().splitlines()[-1].startswith("[bench]")


def probe(monkeypatch, name, read, kernel=None):
    """A reader module ``bench.metrics.<name>`` that exists only in the
    test, and the tiny cell with it among its per-layer metrics."""
    stub = types.ModuleType(f"bench.metrics.{name}")
    stub.read = read
    if kernel:
        stub.KERNEL = kernel
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    cell, cfg, mix, cell_data, specs, e2e, layer = tiny.loaded()
    specs = dict(specs, **{name: {"name": name, "unit": "count"}})
    return cell, cfg, mix, cell_data, specs, e2e, layer + [name]


def test_window_counters_reach_a_new_reader(capsys, monkeypatch):
    seen = []
    loaded = probe(monkeypatch, "window_probe", seen.append)
    rc = run.main(ARGS, require_tpu=False, mc=tiny.model_config(), loaded=loaded)
    assert rc == 0 and json.loads(capsys.readouterr().out.splitlines()[-1])["correct"]
    (r,) = seen
    steps = len(r.window_steps)
    assert steps > 0 and r.span_n["engine.step"] == steps
    assert 0 < r.span_s["engine.fetch"] < r.span_s["engine.step"]
    # the registered counters, as changes over the window and at its close
    assert r.counters["engine_steps"] == r.counters["span_n.engine.step"] == steps
    assert r.counters["span_s.engine.step"] == pytest.approx(r.span_s["engine.step"])
    assert r.counters_at_close["engine_steps"] > steps
    assert r.counters_at_close["kv_pool_pages"] == 64
    assert r.scope_s is None and r.trace is None


def test_traced_run_hands_a_new_reader_its_kernel_and_counters(capsys, monkeypatch):
    asked = []
    reduce = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce",
                        lambda ev, kernels=(), **k: asked.append(kernels) or reduce(
                            ev, kernels, **k))
    seen = []
    loaded = probe(monkeypatch, "expert_tokens",
                   lambda r: seen.append(r) or r.counters["engine_steps"],
                   kernel="expert_matmul")
    rc = run.main(ARGS[:-1] + ["1"], require_tpu=False, mc=tiny.model_config(),
                  loaded=loaded)
    res = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and res["correct"], res
    assert asked == [("expert_matmul",)]
    (r,) = seen
    assert res["metrics"]["expert_tokens"] == {"value": len(r.window_steps), "unit": "count"}
    assert r.trace is not None and r.scope_s is not None
    assert res["device"]["window_s"] > 0
