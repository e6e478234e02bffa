"""The control fails the comparison: with ``--control 1`` the reference
computed in fp8 (the precision below the bf16 the cells serve in) is put in
the program's place, and the run's own ``correct`` comes out false at the
tiny cell's limit, while a sound run of the program on the same seed stays
correct (``test_harness``). The chip-size readings that set each cell's
limit are in PERF.md."""
import json

from bench import run
from bench.tests import tiny


def test_fp8_control_is_not_correct(capsys):
    rc = run.main(["--workload", "tiny.cell", "--seed", str(2**33 + 5), "--seconds", "3",
                   "--trace", "0", "--control", "1"],
                  require_tpu=False, mc=tiny.model_config(), loaded=tiny.loaded())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and not res["correct"], res
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"] == tiny.CELL_DATA["logit_gap_limit"], gap
