"""Readings that set a cell's limits: the program's on many seeds, and the
control's on a few, in one process.

    python bench/control.py --workload <cell> --seeds 12 --control-seeds 3

For each program seed it runs one iteration of the timed path at the
cell's own size and compares the sampled cells with the plain reference,
as a benchmark run does. For each control seed it puts the reference,
computed in bfloat16 (the nearest precision below the configuration's
float32), in the program's place and compares it the same way. It prints
one JSON object per seed and a summary: the largest reading of the
program (the lower reading of each limit) and the smallest of the
control (the upper reading). The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".jax_cache")
os.makedirs(CACHE, exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from benchlib import catalog, cell, check  # noqa: E402


def readings(c, pts, got) -> dict:
    want = cell.reference_results(c, pts)
    cmp = check.compare(got, want, [p.key for p in pts])
    return {"counter_mismatch": cmp.counter_mismatch,
            "float_rel_gap": cmp.float_rel_gap, "worst": cmp.worst,
            "bad_cells": cmp.bad_cells}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7_000_000_001)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    c = catalog.load_cell(args.workload)
    from repro import compile_cache
    compile_cache.enable()
    prog = cell.Program(c)
    n = int(c.traffic["reference_cells"])
    prog_r, ctrl_r = [], []
    for k in range(args.seeds):
        seed = args.seed + 1000 * k
        pts = prog.points(c.traffic, cell.iteration_seeds(c.traffic, seed, 0))
        t0 = time.perf_counter()
        res, _ = prog.sweep(pts)
        t1 = time.perf_counter()
        win = cell.window.Window(t0, t1, [cell.window.Iteration(
            0, t0, t1, pts, res, [])])
        spts, got = cell.sample_cells(win, seed, n)
        r = readings(c, spts, got) | {
            "seed": seed, "side": "program", "missing": win.missing,
            "sweep_s": t1 - t0, "ref_s": time.perf_counter() - t1}
        prog_r.append(r)
        print(json.dumps(r), flush=True)
    for k in range(args.control_seeds):
        seed = args.seed + 1000 * (args.seeds + k)
        pts = prog.points(c.traffic, cell.iteration_seeds(c.traffic, seed, 0))
        import random
        rng = random.Random(seed)
        spts = pts if n >= len(pts) else rng.sample(pts, n)
        t0 = time.perf_counter()
        # on the chip: bfloat16 is emulated, and slow, on the host's CPU
        import jax
        got = cell.reference_results(c, spts, ftype="bfloat16",
                                     device=jax.devices()[0])
        r = readings(c, spts, got) | {
            "seed": seed, "side": "control", "s": time.perf_counter() - t0}
        ctrl_r.append(r)
        print(json.dumps(r), flush=True)
    summary = {"workload": c.name, "program_seeds": len(prog_r),
               "control_seeds": len(ctrl_r)}
    for key in ("counter_mismatch", "float_rel_gap"):
        if prog_r:
            summary[f"lower.{key}"] = max(r[key] for r in prog_r)
        if ctrl_r:
            summary[f"upper.{key}"] = min(r[key] for r in ctrl_r)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
