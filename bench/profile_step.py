"""A cell's traced run:

    python3 bench/profile_step.py --workload <cell> --seed <n> --seconds <s>

is ``bench/run.py`` with ``--trace 1``, which reads the engine's spans and
counters over its window, device seconds by named scope and idle time by
host span, and logs them on its ``[bench]`` lines. This name stays while
docs/observability.md cites it.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(sys.argv[1:] + ["--trace", "1"]))
