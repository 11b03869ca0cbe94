#!/usr/bin/env python
"""Single-cell step-engine throughput benchmark (DESIGN.md §12).

Times the three step-engine paths warm over the daily MSR traces, one
cell at a time (the configuration where the per-op scan's O(n_logical)
residency traffic dominates):

  per_op     — the seed-identical per-op `lax.scan` (`sim.run_trace`)
  compressed — event-compressed segment scan (`sim.run_compressed`)
  packed     — the same plus the int16-packed carry

Ops/s always credits the ORIGINAL padded length T, so pad-tail trimming
shows up as throughput rather than as shrunk work, and the speedup
column is directly the wall-clock ratio on identical (bit-identical —
tests/test_compress.py) simulations.

Writes BENCH_step_throughput.json (schema checked by
`sweep.store.check_step_throughput`; also the CI gate's input —
scripts/ci_check.sh runs a truncated version with --min-speedup 3), and
appends one attributable (git-SHA-keyed) record per run to
BENCH_history.json (`repro.telemetry.history`). Each per-trace timing
is a `telemetry.spans` span — pass --chrome-trace to export the span
tree for chrome://tracing / Perfetto.

--timeline-overhead-check [WINDOW] additionally times the compressed
path with segment-aware telemetry attached (DESIGN.md §13) against
telemetry-off, interleaved warm pairs, and records the per-trace +
geomean ratio; --max-timeline-overhead gates it (the CI ≤1.3x gate).

Usage:
  PYTHONPATH=src python scripts/bench_step.py                 # full, 11 traces
  PYTHONPATH=src python scripts/bench_step.py \
      --traces hm_0,proj_0 --max-ops 32768 --min-speedup 3    # CI smoke
  PYTHONPATH=src python scripts/bench_step.py --traces hm_0 \
      --timeline-overhead-check --max-timeline-overhead 1.3
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _time_warm(fn, reps: int) -> float:
    fn()                                   # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--traces", default=None,
                    help="comma-separated MSR trace names (default: all)")
    ap.add_argument("--policy", default="ips_agc")
    ap.add_argument("--mode", default="daily", choices=("daily", "bursty"))
    ap.add_argument("--max-ops", type=int, default=None,
                    help="truncate traces (CI smoke)")
    ap.add_argument("--scale", type=int, default=128)
    ap.add_argument("--reps", type=int, default=1,
                    help="timed repetitions after warmup")
    ap.add_argument("--out-dir", default=".")
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="fail unless compressed geomean speedup >= this")
    ap.add_argument("--timeline-overhead-check", nargs="?", const=1024,
                    type=int, default=None, metavar="WINDOW_OPS",
                    help="also time the compressed path with segment "
                    "telemetry attached (DESIGN.md §13), interleaved warm "
                    "pairs vs telemetry-off (default window: 1024 ops)")
    ap.add_argument("--max-timeline-overhead", type=float, default=0.0,
                    help="fail unless the telemetry-on/off geomean wall "
                    "ratio <= this (CI gate: 1.3)")
    ap.add_argument("--chrome-trace", default=None, metavar="PATH",
                    help="export the run's span tree as a Chrome "
                    "trace-event file")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the BENCH_history.json append")
    args = ap.parse_args(argv)

    import repro.workloads as wl
    from repro import compile_cache
    from repro.configs.ssd_paper import PAPER_SSD
    from repro.core.ssd import sim
    from repro.core.ssd.policies.state import can_pack, default_cell
    from repro.core.ssd.policies.registry import resolve_spec
    from repro.sweep.report import geomean
    from repro.sweep.runner import _n_logical
    from repro.sweep.store import (_git_sha, check_step_throughput,
                                   save_bench)
    from repro.telemetry import Tracer, chrome_trace
    from repro.telemetry.spans import span
    from repro.workloads.compress import compress_ops

    if (args.max_timeline_overhead
            and args.timeline_overhead_check is None):
        ap.error("--max-timeline-overhead requires "
                 "--timeline-overhead-check")

    compile_cache.enable()
    cfg = PAPER_SSD.scaled(args.scale)
    n_logical, capacity = _n_logical(cfg), cfg.total_pages
    closed = args.mode == "bursty"
    names = (args.traces.split(",") if args.traces
             else list(wl.TRACE_NAMES))
    params = default_cell(cfg, resolve_spec(args.policy))

    tracer = Tracer()
    traces = {}
    with tracer.activate():
        for name in names:
            ops = wl.build_ops(name, n_logical, mode=args.mode,
                               capacity_pages=capacity)
            if args.max_ops:
                ops = wl.truncate_trace(ops, args.max_ops)
            t_len = int(ops["arrival_ms"].shape[0])
            comp = compress_ops(ops)

            def per_op():
                lat, st = sim.run_trace(cfg, args.policy, ops,
                                        closed_loop=closed,
                                        n_logical=n_logical, params=params)
                lat.block_until_ready()

            def compressed(packed=False, timeline_ops=None):
                lat, st = sim.run_compressed(cfg, args.policy, comp,
                                             closed_loop=closed,
                                             n_logical=n_logical,
                                             params=params, packed=packed,
                                             timeline_ops=timeline_ops)
                lat.block_until_ready()
                if timeline_ops is not None:
                    # telemetry must be materialized, not just dispatched
                    st.timeline.ctr.block_until_ready()

            pack_ok = can_pack(cfg, n_logical, params)
            row = {"t_len": t_len, "t_trim": comp.t_trim,
                   "fill": comp.fill, "n_pad": comp.n_pad}
            for label, fn in (("per_op", per_op),
                              ("compressed", compressed),
                              ("packed",
                               (lambda: compressed(True)) if pack_ok
                               else compressed)):
                with span(f"bench.{label}", "bench", trace=name,
                          t_len=t_len):
                    warm = _time_warm(fn, args.reps)
                row[label] = {"warm_s": round(warm, 4),
                              "ops_per_s": round(t_len / warm, 1)}
            row["speedup_compressed"] = round(
                row["compressed"]["ops_per_s"]
                / row["per_op"]["ops_per_s"], 2)
            row["speedup_packed"] = round(
                row["packed"]["ops_per_s"] / row["per_op"]["ops_per_s"], 2)
            if args.timeline_overhead_check is not None:
                # interleaved off/on warm pairs, median of 5: background
                # load drifts on the scale of one pass and sequential
                # one-shot timings alias that drift into the ratio; each
                # timed sample is repped up to ~0.3s because a sub-100ms
                # sample aliases scheduler noise into the ratio too
                wo = args.timeline_overhead_check
                tl_on = lambda: compressed(timeline_ops=wo)  # noqa: E731
                compressed(), tl_on()          # warm both programs
                est = _time_warm(compressed, 1)
                inner = max(args.reps,
                            int(np.ceil(0.3 / max(est, 1e-3))))
                offs, ons = [], []
                with span("bench.timeline_overhead", "bench", trace=name,
                          window_ops=wo, inner_reps=inner):
                    for _ in range(5):
                        offs.append(_time_warm(compressed, inner))
                        ons.append(_time_warm(tl_on, inner))
                off_med, on_med = sorted(offs)[2], sorted(ons)[2]
                row["timeline_overhead"] = {
                    "window_ops": wo,
                    "off_warm_s": round(off_med, 4),
                    "on_warm_s": round(on_med, 4),
                    "ratio": round(on_med / max(off_med, 1e-9), 4)}
            traces[name] = row
            print(f"{name:>8}: T={t_len} trim={comp.t_trim} "
                  f"per_op {row['per_op']['ops_per_s'] / 1e6:.3f} -> "
                  f"compressed {row['compressed']['ops_per_s'] / 1e6:.3f} "
                  f"({row['speedup_compressed']:.2f}x) -> packed "
                  f"{row['packed']['ops_per_s'] / 1e6:.3f} Mops/s "
                  f"({row['speedup_packed']:.2f}x)"
                  + (f"  tl x{row['timeline_overhead']['ratio']:.3f}"
                     if "timeline_overhead" in row else ""))

    doc = {
        "policy": args.policy, "mode": args.mode,
        "max_ops": args.max_ops, "scale": args.scale, "reps": args.reps,
        "git_sha": _git_sha(),
        "traces": traces,
        "spans": tracer.to_json(),
        "geomean_speedup": {
            "compressed": round(geomean(
                r["speedup_compressed"] for r in traces.values()), 2),
            "packed": round(geomean(
                r["speedup_packed"] for r in traces.values()), 2)},
    }
    gm = doc["geomean_speedup"]
    print(f"geomean speedup: compressed {gm['compressed']:.2f}x, "
          f"packed {gm['packed']:.2f}x")
    tl_ratio = None
    if args.timeline_overhead_check is not None:
        tl_ratio = round(geomean(
            r["timeline_overhead"]["ratio"] for r in traces.values()), 4)
        doc["geomean_timeline_overhead"] = tl_ratio
        print(f"geomean compressed-telemetry overhead: x{tl_ratio:.3f}"
              + (f" (gate {args.max_timeline_overhead:.2f})"
                 if args.max_timeline_overhead else ""))
    if args.chrome_trace:
        print(f"wrote {chrome_trace(tracer.to_json(), args.chrome_trace)}")
    if not args.no_save:
        path = save_bench("step_throughput", doc, directory=args.out_dir,
                          cfg=cfg)
        print(f"saved {path}")
        check_step_throughput(__import__("json").load(open(path)),
                              min_speedup=args.min_speedup)
    elif args.min_speedup:
        assert gm["compressed"] >= args.min_speedup, (
            f"compressed geomean speedup {gm['compressed']:.2f}x < "
            f"{args.min_speedup:.2f}x")
    if not args.no_history:
        from repro.telemetry import history
        rec = history.append_record(
            "bench_step", f"{args.policy}/{args.mode}"
                          f":max_ops={args.max_ops}"
                          f":traces={','.join(names)}",
            directory=args.out_dir, git_sha=doc["git_sha"],
            ops_per_s=geomean(r["compressed"]["ops_per_s"]
                              for r in traces.values()),
            meta={"speedup_compressed": gm["compressed"],
                  "speedup_packed": gm["packed"],
                  **({"timeline_overhead": tl_ratio}
                     if tl_ratio is not None else {})})
        print(f"history: appended {rec['kind']}:{rec['config']} "
              f"@ {str(rec['git_sha'])[:12]}")
    if args.max_timeline_overhead:
        assert tl_ratio <= args.max_timeline_overhead, (
            f"compressed-telemetry overhead x{tl_ratio:.3f} exceeds the "
            f"x{args.max_timeline_overhead:.2f} gate")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
