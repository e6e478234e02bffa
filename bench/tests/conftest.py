"""Put the checkout root and the program's ``src`` on the path, as
``bench/run.py`` does, and keep the CPU tests off any persistent compile
cache."""
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
jax.config.update("jax_enable_compilation_cache", False)
