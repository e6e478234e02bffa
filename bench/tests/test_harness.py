"""The harness end to end on the CPU at tiny widths, without the look for a
chip: a sound run is correct, and each fault a served cell can have, planted
under the timed path, turns ``correct`` false.

- state: the step returns the KV pool it was given (nothing is written);
- token: every fifth sampled token is replaced where it is produced;
- half: half of the sampled rows take the other half's logits (their own
  computation left out).
"""
import json

import numpy as np
import pytest

from bench import run
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
