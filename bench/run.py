"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic, its metrics and its plain
reference are found by name from `BENCHMARK.json`
(`bench/benchlib/catalog.py`). The run needs
the chips the cell asks for: without them it exits 2 and prints no
result. JAX's compilation cache is kept in `.jax_cache/` at the root of
the checkout, so only a checkout's first run of a cell compiles.
"""
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".jax_cache")
os.makedirs(CACHE, exist_ok=True)     # JAX writes no entry into a missing one
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from benchlib import cell  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(cell.main(t_start=T_START))
