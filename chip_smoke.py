#!/usr/bin/env python
"""Chip smoke test: the sweep path end to end on a TPU, in one process.

Runs the simulator's main path, `sweep.runner.run_sweep` (trace build,
fleet dispatch, `vmap(lax.scan)`, pad replay, summary), at the paper's
drive scale with full trace lengths, and checks what comes out:

* the whole paper grid (102 cells) and a per-op slice of the endurance
  and host-tier grids, cell for cell against the committed
  `BENCH_sweep_*.json` artifacts: integer-valued counters exactly, float
  summaries and the paper geomeans within `FLOAT_RTOL`;
* four `hm_0` cells through the per-op single-cell reference
  `driver.eval_cell`, against the fleet's results for the same cells.

Usage, from the checkout root, on a machine with a TPU:

  python chip_smoke.py              # one chip: every phase above
  python chip_smoke.py --chips 4    # the paper grid sharded over four
                                    # chips, plus the reference cells

It exits non-zero, and prints no result, unless JAX's first device is a
TPU. Per-group compile counts and dispatch/block seconds are printed as
information, not as a benchmark. The last line of a passing run is one
JSON object naming the device. Nothing is written into the checkout but
the compilation cache (`repro.compile_cache`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# counters that are integers carried in float32: they must match exactly
INT_METRICS = ("host_pages", "slc_writes", "tlc_writes", "migrations",
               "erases", "reprogram_host", "reprogram_agc",
               "reprogram_trad", "n_ops")
# relative tolerance on every other summary (means, ratios, geomeans)
FLOAT_RTOL = 1e-5
REF_TRACE = "hm_0"
REF_CELLS = [(mode, policy) for mode in ("daily", "bursty")
             for policy in ("baseline", "ips")]


def log(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def check_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found platform "
                         f"{dev['platform']!r} ({dev['kind']}), not a TPU")
    if dev["count"] != chips:
        raise SystemExit(f"chip_smoke: {dev['count']} TPU device(s) "
                         f"present, this run needs {chips} (--chips)")
    from repro.sweep.store import _run_meta
    meta = _run_meta()
    if meta["backend"] != "tpu":
        raise SystemExit(f"chip_smoke: sweep metadata records backend "
                         f"{meta['backend']!r}, not 'tpu'")
    log(f"device {dev['kind']} x{dev['count']}, jax {jax.__version__}, "
        f"git_sha {meta['git_sha']}")
    return dev


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    `jax.monitoring` (backend compile time includes cache reads)."""

    def __init__(self):
        import jax
        self.compile_s, self.compiles = 0.0, 0
        self.hits = self.misses = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"{self.compiles} backend compile(s), "
                f"{self.compile_s:.2f} s; persistent cache "
                f"{self.hits} hit(s), {self.misses} miss(es)")


def run_cells(label: str, cfg, points, trace_cache):
    """run_sweep over `points`; returns (results, per-group timings)."""
    from repro.core.ssd import fleet
    from repro.sweep.runner import run_sweep
    timings: list[dict] = []
    t0 = time.perf_counter()
    results = run_sweep(cfg, points, trace_cache=trace_cache,
                        timings=timings)
    log(f"{label}: {len(points)} cells, {len(timings)} groups, "
        f"{time.perf_counter() - t0:.2f} s wall, shard_skipped "
        f"{fleet.shard_skip_count()}")
    for g in timings:
        print(f"  group {g['policies']}/{g['mode']} cells={g['cells']}"
              f"+{g['pad']} t_len={g['t_len']} t_scan={g['t_scan']} "
              f"exec_path={g['exec_path']} devices={g['devices']} "
              f"compiles={g['compiles']} "
              f"dispatch_s={g['dispatch_s']} block_s={g['block_s']}",
              flush=True)
    return results, timings


class Diff:
    """Collects mismatches and the largest relative float difference seen
    per metric, so one run reports every disagreement at once."""

    def __init__(self):
        self.bad: list[str] = []
        self.max_rel: dict[str, float] = {}

    def cell(self, where: str, got: dict, want: dict) -> None:
        if set(got) != set(want):
            self.bad.append(f"{where}: metric keys differ "
                            f"{sorted(set(got) ^ set(want))}")
        for k in sorted(set(got) & set(want)):
            self.value(f"{where} {k}", k, got[k], want[k])

    def value(self, where: str, metric: str, got, want) -> None:
        if metric in INT_METRICS:
            if got != want:
                self.bad.append(f"{where}: {got!r} != {want!r}")
            return
        rel = abs(got - want) / max(abs(want), 1e-12)
        self.max_rel[metric] = max(self.max_rel.get(metric, 0.0), rel)
        if rel > FLOAT_RTOL:
            self.bad.append(f"{where}: {got!r} vs {want!r} (rel {rel:.3e})")

    def report(self, label: str) -> list[str]:
        worst = ", ".join(f"{k} {v:.3e}" for k, v in sorted(
            self.max_rel.items()))
        log(f"{label}: {len(self.bad)} mismatch(es); max rel diff: "
            f"{worst or 'none'}")
        return [f"{label}: {b}" for b in self.bad]


def against_artifact(label: str, results: dict, artifact: str, *,
                     geomeans: bool) -> list[str]:
    from repro.sweep.report import policy_geomeans
    with open(os.path.join(ROOT, artifact)) as f:
        doc = json.load(f)
    d = Diff()
    for pt, got in results.items():
        want = doc["results"].get(pt.key)
        if want is None:
            d.bad.append(f"{pt.key}: not in {artifact}")
        else:
            d.cell(pt.key, got, want)
    if geomeans:
        gm = {f"{m}/{p}": v for (m, p), v in policy_geomeans(results).items()}
        if set(gm) != set(doc["geomeans"]):
            d.bad.append(f"geomean keys differ from {artifact}")
        for key in sorted(set(gm) & set(doc["geomeans"])):
            for metric, want in doc["geomeans"][key].items():
                if metric != "n":
                    d.value(f"geomean {key}", metric, gm[key][metric], want)
    return d.report(f"{label} vs {artifact}")


def against_reference(cfg, results: dict) -> list[str]:
    from repro.core.ssd.driver import eval_cell
    from repro.sweep.grid import SweepPoint
    d = Diff()
    t0 = time.perf_counter()
    for mode, policy in REF_CELLS:
        pt = SweepPoint(trace=REF_TRACE, mode=mode, policy=policy)
        d.cell(pt.key, results[pt], eval_cell(cfg, REF_TRACE, policy, mode))
    log(f"eval_cell reference: {len(REF_CELLS)} cells, "
        f"{time.perf_counter() - t0:.2f} s wall")
    return d.report("fleet vs eval_cell")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paper grid sharded over four "
                    "chips and the reference cells it is compared with")
    args = ap.parse_args(argv)

    dev = check_device(args.chips)
    from repro import compile_cache
    log(f"compilation cache: {compile_cache.enable()}")
    meter = CompileMeter()

    from repro import workloads
    from repro.configs.ssd_paper import PAPER_SSD
    from repro.core.ssd import fleet
    from repro.core.ssd.driver import DEFAULT_SCALE
    from repro.sweep import grid

    cfg = PAPER_SSD.scaled(DEFAULT_SCALE)
    cache = workloads.TraceCache(use_disk=False)
    bad: list[str] = []

    paper, timings = run_cells("paper grid", cfg, grid.paper_grid(), cache)
    spans = sorted({g["devices"] for g in timings})
    if fleet.shard_skip_count() or spans != [args.chips]:
        bad.append(f"paper grid: fleets spanned {spans} device(s), "
                   f"shard_skipped {fleet.shard_skip_count()}; want "
                   f"[{args.chips}] and 0")
    bad += against_artifact("paper grid", paper, "BENCH_sweep_paper.json",
                            geomeans=True)
    bad += against_reference(cfg, paper)

    if args.chips == 1:
        endur, _ = run_cells("endurance hm_0", cfg, [
            p for p in grid.endurance_grid() if p.trace == REF_TRACE], cache)
        bad += against_artifact("endurance", endur,
                                "BENCH_sweep_endurance.json", geomeans=False)
        host, _ = run_cells("host tier", cfg, [
            p for p in grid.hostcache_grid()
            if p.policy in ("baseline", "ips") and p.hostcache is not None
            and p.hostcache.tag == "wb:watermark"], cache)
        bad += against_artifact("host tier", host,
                                "BENCH_sweep_hostcache.json", geomeans=False)

    log(f"compile totals: {meter.line()}")
    if bad:
        for line in bad:
            print(f"MISMATCH {line}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
