"""Sweep CLI: `python -m repro.sweep.cli --grid paper` reproduces the
paper's evaluation (Figs. 9-12) in one batched invocation.

Examples (run with PYTHONPATH=src):

  python -m repro.sweep.cli --grid paper            # full figure set
  python -m repro.sweep.cli --grid quick --max-ops 8192   # CI smoke gate
  python -m repro.sweep.cli --grid stress           # generator scenarios
  python -m repro.sweep.cli --grid mixed            # multi-tenant + CIs
  python -m repro.sweep.cli --grid beyond           # beyond-paper policies
  python -m repro.sweep.cli --grid matrix --bench   # + fleet-vs-loop bench
  python -m repro.sweep.cli --traces hm_0,gc_pressure --seeds 0,1,2
  python -m repro.sweep.cli --trace-file traces/a.csv --policies ips,ips_agc
  python -m repro.sweep.cli --grid quick --policies dyn_slc,ips_lazy
      # registry smoke: replay a named grid's workloads under any
      # registered policies (declared baselines are added automatically)
  python -m repro.sweep.cli --grid endurance      # wear/lifetime columns
  python -m repro.sweep.cli --grid hostcache      # host-tier cache columns
  python -m repro.sweep.cli --traces hm_0 --hostcache mode=wb,flush=idle
      # host cache knobs on a custom grid (DESIGN.md §14)
  python -m repro.sweep.cli --grid sensitivity    # one-axis deltas vs ips
  python -m repro.sweep.cli --traces hm_0 --policies ips,ips_raro \
      --endurance w_rp=4,rp_budget=2   # endurance knobs on a custom grid
  python -m repro.sweep.cli --list-policies   # registry: name/composition
  python -m repro.sweep.cli --list-grids      # named grids + cell counts
  python -m repro.sweep.cli --search quick    # policy+scenario autotuning
      # (repro.search, DESIGN.md §10): successive-halving over the
      # composition x knob space to a Pareto front (latency/WAF/TBW vs
      # declared baselines) + adversarial scenario search; writes
      # BENCH_search.json with per-round survivor/compile counts
  python -m repro.sweep.cli --search smoke --search-scenario ips:coop

Policies resolve through the mechanism-composition registry
(`repro.core.ssd.policies`): any registered name — the four paper schemes
plus beyond-paper compositions like dyn_slc / ips_lazy — is valid for
--policies, and each cell normalizes against its policy's declared
baseline (DESIGN.md §8).

Workload specs resolve through `repro.workloads`: MSR trace names,
scenario-generator names (zipf_hot, diurnal, read_burst, gc_pressure,
tenant_mix) and trace-file paths (--trace-file, or any --traces entry with
a path separator) all run through the same fleet path. Trace tensors are
memoized by the content-addressed compiled-trace cache; hit/miss counts
land in the BENCH_*.json run metadata. With more than one --seeds value,
geomean summaries gain bootstrap confidence intervals.

Device sharding: before importing jax the CLI forces
`--xla_force_host_platform_device_count=<n>` (default: all CPUs) so the
fleet's cell axis shards across host devices; pass --devices 1 to disable.
The flag shapes only JAX's CPU platform: on a TPU machine the cells shard
over the chips. The compilation cache is placed by `repro.compile_cache`.
Results land in `BENCH_<name>.json` (sweep.store) for the cross-PR perf
trajectory.
"""
from __future__ import annotations

import argparse
import os
import sys

# jax-free at module level (XLA_FLAGS must be pinned before jax imports);
# grid and workloads are numpy-only
from repro.sweep.grid import GRIDS


def _parse(argv):
    ap = argparse.ArgumentParser(
        prog="repro.sweep.cli",
        description="Batched parameter sweeps over the hybrid-SSD fleet "
                    "simulator (paper Figs. 9-12).")
    ap.add_argument("--grid", choices=tuple(GRIDS),
                    default=None, help="named grid; omit to build one from "
                    "--traces/--policies/--modes")
    ap.add_argument("--traces", default=None,
                    help="comma list of workload specs: MSR names, "
                    "scenario names, or trace-file paths "
                    "(default: all 11 MSR traces)")
    ap.add_argument("--trace-file", action="append", default=[],
                    metavar="PATH", help="add a real trace file (MSR CSV, "
                    "generic CSV, fio iolog; .gz/.zst ok) as a workload; "
                    "repeatable")
    ap.add_argument("--policies", default=None,
                    help="comma list of registered policy names (default: "
                    "baseline,ips,ips_agc); combined with --grid it "
                    "replays the grid's workload cells under these "
                    "policies + their declared baselines")
    ap.add_argument("--modes", default="bursty,daily")
    ap.add_argument("--endurance", nargs="?", const="", default=None,
                    metavar="K=V[,K=V...]",
                    help="enable wear/reliability tracking on every cell "
                    "(DESIGN.md §9); optional knobs over EnduranceSpec "
                    "fields, e.g. w_rp=4,rp_budget=2,cycle_budget=60,"
                    "read_penalty_ms=0.05 (bare flag: defaults). "
                    "Overrides a named grid's pinned knobs")
    ap.add_argument("--hostcache", nargs="?", const="", default=None,
                    metavar="K=V[,K=V...]",
                    help="put the host-tier block cache (DESIGN.md §14) in "
                    "front of every cell; optional knobs over "
                    "HostCacheSpec fields, e.g. mode=wb,flush=watermark,"
                    "sets=128,ways=8,wm_hi=0.75 (bare flag: write-back "
                    "defaults). Overrides a named grid's pinned specs")
    ap.add_argument("--search", choices=("smoke", "quick", "full"),
                    default=None, metavar="BUDGET",
                    help="run the search engine (repro.search) instead of "
                    "a sweep: successive-halving policy autotuning to a "
                    "Pareto front + adversarial scenario search at the "
                    "named budget (smoke|quick|full); writes "
                    "BENCH_search.json")
    ap.add_argument("--search-scenario", default="ips:baseline",
                    metavar="A:B", help="policy pair for the scenario "
                    "search (default ips:baseline); 'none' skips it")
    ap.add_argument("--list-policies", action="store_true",
                    help="print the policy registry (name, composition, "
                    "baseline, doc) and exit")
    ap.add_argument("--list-grids", action="store_true",
                    help="print the named grids (name, cells, summary) "
                    "and exit")
    ap.add_argument("--seeds", default="0", help="comma list of RNG seeds; "
                    ">1 seed adds bootstrap CIs to the geomean summary")
    ap.add_argument("--cache-fracs", default="1.0",
                    help="comma list of SLC cache scale factors")
    ap.add_argument("--scale", type=int, default=128,
                    help="drive scale-down factor (DESIGN.md §2)")
    ap.add_argument("--max-ops", type=int, default=None,
                    help="truncate traces (smoke runs)")
    ap.add_argument("--devices", type=int, default=None,
                    help="host device count for cell sharding "
                    "(default: cpu count; 1 disables)")
    ap.add_argument("--no-trace-cache-disk", action="store_true",
                    help="keep the compiled-trace cache in memory only")
    ap.add_argument("--timeline", nargs="?", const=1024, type=int,
                    default=None, metavar="WINDOW_OPS",
                    help="attach the in-scan telemetry probe (DESIGN.md "
                    "§11): per-window latency/occupancy/WAF series + cliff "
                    "detection per cell, written to "
                    "BENCH_<name>_timeline.json (default window: 1024 ops)")
    ap.add_argument("--chrome-trace", default=None, metavar="PATH",
                    help="also write the run's span tree as a Chrome "
                    "trace-event file (chrome://tracing / Perfetto)")
    ap.add_argument("--timeline-overhead-check", action="store_true",
                    help="re-run the sweep warm with telemetry off and on "
                    "and record the wall-time ratio in the timeline "
                    "artifact (CI gate; requires --timeline)")
    ap.add_argument("--history-check", action="store_true",
                    help="after appending this run to BENCH_history.json, "
                    "fail (exit 1) on >20%% throughput drop or any "
                    "geomean-fidelity drift vs the trailing same-config "
                    "baseline (repro.telemetry.history)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the BENCH_history.json append (one-off "
                    "experiments that should not seed a baseline)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the "
                    "sweep into DIR (TensorBoard/Perfetto-openable; "
                    "the run fails if the capture cannot start)")
    ap.add_argument("--bench", action="store_true",
                    help="also wall-clock fleet vs looped eval_cell")
    ap.add_argument("--name", default=None, help="benchmark artifact name "
                    "(default: sweep_<grid>)")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_<name>.json is written")
    ap.add_argument("--no-save", action="store_true")
    return ap.parse_args(argv)


def _force_host_devices(n: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def main(argv=None) -> int:
    args = _parse(argv if argv is not None else sys.argv[1:])
    n_dev = args.devices if args.devices else (os.cpu_count() or 1)
    if n_dev > 1:
        _force_host_devices(n_dev)

    # heavy imports only after XLA_FLAGS is pinned
    from repro import compile_cache, workloads
    from repro.configs.ssd_paper import PAPER_SSD
    from repro.sweep.grid import expand_grid, named_grid
    from repro.sweep.report import (endurance_summary, hostcache_summary,
                                    policy_geomeans, policy_geomeans_ci,
                                    sensitivity_deltas, throughput_table)
    from repro.sweep.runner import bench_fleet_vs_loop, run_sweep
    from repro.sweep.store import save_bench

    from repro.core.ssd.endurance.spec import EnduranceSpec
    from repro.core.ssd.policies import baseline_of, get_entry, policy_names
    compile_cache.enable()

    if args.list_policies:
        print(f"{'policy':<10}{'composition':<42}{'baseline':<10}doc")
        for name in policy_names():
            e = get_entry(name)
            doc = e.doc.partition(";")[0].partition(":")[0]
            print(f"{name:<10}{e.spec.composition:<42}{e.baseline:<10}"
                  f"{doc}")
        return 0
    if args.list_grids:
        print(f"{'grid':<13}{'cells':>6}  summary")
        for gname, fn in GRIDS.items():
            summary = (fn.__doc__ or "").strip().splitlines()[0]
            print(f"{gname:<13}{len(fn()):>6}  {summary}")
        return 0

    endurance = (None if args.endurance is None
                 else EnduranceSpec.parse(args.endurance))
    if args.hostcache is None:
        hostcache = None
    else:
        from repro.hostcache.spec import HostCacheSpec
        try:
            hostcache = HostCacheSpec.parse(args.hostcache)
        except ValueError as e:
            print(f"error: --hostcache: {e}", file=sys.stderr)
            return 2
    cfg = PAPER_SSD.scaled(args.scale)
    seeds = tuple(int(s) for s in args.seeds.split(","))

    if args.search:
        conflicts = [flag for flag, used in (
            ("--grid", args.grid), ("--traces", args.traces),
            ("--trace-file", args.trace_file),
            ("--policies", args.policies),
            ("--endurance", args.endurance is not None),
            ("--hostcache", args.hostcache is not None),
            ("--modes", args.modes != "bursty,daily"),
            ("--cache-fracs", args.cache_fracs != "1.0"),
            ("--bench", args.bench),
            ("--timeline", args.timeline is not None),
            ("--timeline-overhead-check", args.timeline_overhead_check),
            ("--seeds (search scores one seed)", len(seeds) > 1),
        ) if used]
        if conflicts:
            print("error: --search runs its own candidate space and "
                  "round schedule (repro.search.SPACES/SCHEDULES); drop "
                  + ", ".join(conflicts), file=sys.stderr)
            return 2
        return _run_search(args, cfg, seeds[0])
    if args.search_scenario != "ips:baseline":
        print("error: --search-scenario only applies to --search runs",
              file=sys.stderr)
        return 2

    def check_policies(policies) -> bool:
        unknown = sorted(set(policies) - set(policy_names()))
        if unknown:
            print(f"error: unknown --policies value(s) "
                  f"{','.join(unknown)}; registered: "
                  f"{','.join(policy_names())}", file=sys.stderr)
            return False
        return True

    if args.grid:
        if args.trace_file:
            print("error: --trace-file cannot be combined with --grid "
                  "(named grids fix their workloads); drop --grid or pass "
                  "the file via --traces/--trace-file alone",
                  file=sys.stderr)
            return 2
        points = named_grid(args.grid)
        if args.policies:
            # registry smoke path: replay the grid's workload cells under
            # the requested policies, auto-adding each policy's declared
            # baseline so the normalized table stays meaningful
            req = tuple(dict.fromkeys(args.policies.split(",")))
            if not check_policies(req):
                return 2
            wanted = list(dict.fromkeys(
                sum(((p, baseline_of(p)) for p in req), ())))
            coords = list(dict.fromkeys(
                (pt.trace, pt.mode, pt.seed, pt.repeat, pt.cache_frac,
                 pt.idle_threshold_ms, pt.cap_boost_frac, pt.endurance,
                 pt.hostcache)
                for pt in points))
            from repro.sweep.grid import SweepPoint
            points = [SweepPoint(trace=t, mode=m, policy=p, seed=s,
                                 repeat=r, cache_frac=c,
                                 idle_threshold_ms=i, cap_boost_frac=b,
                                 endurance=e, hostcache=h,
                                 baseline=baseline_of(p))
                      for (t, m, s, r, c, i, b, e, h) in coords
                      for p in wanted]
    else:
        traces = tuple((args.traces.split(",") if args.traces else
                        (workloads.TRACE_NAMES if not args.trace_file
                         else ())))
        traces += tuple(args.trace_file)
        policies = tuple((args.policies or "baseline,ips,ips_agc")
                         .split(","))
        modes = tuple(args.modes.split(","))
        bad, missing, file_specs = [], [], []
        for t in sorted(set(traces)):
            try:
                kind = workloads.spec_kind(t)
            except ValueError:
                bad.append(t)
                continue
            if kind == "file":
                file_specs.append(t)
                if not os.path.isfile(t):
                    missing.append(t)
        if bad or missing:
            if bad:
                print(f"error: unknown --traces value(s) {','.join(bad)}; "
                      f"valid: {','.join(workloads.known_specs())} "
                      "(or a trace-file path)", file=sys.stderr)
            for path in missing:
                print(f"error: trace file not found: {path}",
                      file=sys.stderr)
            return 2
        if file_specs and len(seeds) > 1:
            print("note: file-backed traces are deterministic — the seed "
                  "axis only varies synthetic/scenario cells",
                  file=sys.stderr)
        if not check_policies(policies):
            return 2
        # fail fast on a normalization hole: outside --grid replay there is
        # no auto-add, so a policy whose declared baseline is excluded
        # would silently produce no normalized rows/geomeans
        orphans = {p: baseline_of(p) for p in policies
                   if baseline_of(p) not in policies}
        if orphans:
            for pol, base in sorted(orphans.items()):
                print(f"error: policy {pol!r} normalizes against {base!r}, "
                      "which is not in --policies — its cells would have "
                      "nothing to normalize to; add the baseline, e.g. "
                      f"--policies {','.join(dict.fromkeys((*policies, base)))} "
                      "(baselines are auto-added only in --grid replay)",
                      file=sys.stderr)
            return 2
        unknown_modes = sorted(set(modes) - {"bursty", "daily"})
        if unknown_modes:
            print(f"error: unknown --modes value(s) "
                  f"{','.join(unknown_modes)}; valid: bursty,daily",
                  file=sys.stderr)
            return 2
        if not traces:
            print("error: no workloads selected", file=sys.stderr)
            return 2
        from dataclasses import replace
        points = [replace(pt, baseline=baseline_of(pt.policy))
                  for pt in expand_grid(
                      traces=traces, modes=modes, policies=policies,
                      seeds=seeds,
                      cache_fracs=tuple(float(c) for c in
                                        args.cache_fracs.split(",")))]

    if endurance is not None:
        from dataclasses import replace
        points = [replace(pt, endurance=endurance) for pt in points]
    if hostcache is not None:
        from dataclasses import replace
        points = [replace(pt, hostcache=hostcache) for pt in points]

    if args.timeline_overhead_check and not args.timeline:
        print("error: --timeline-overhead-check requires --timeline",
              file=sys.stderr)
        return 2
    if args.timeline is not None and args.timeline <= 0:
        print("error: --timeline wants a positive window size (ops)",
              file=sys.stderr)
        return 2

    import contextlib

    from repro.core.ssd import fleet
    from repro.telemetry import Tracer, chrome_trace, timeline_payload
    from repro.telemetry import timeline as tmod
    from repro.telemetry.spans import span

    tracer = (Tracer() if (args.timeline or args.chrome_trace) else None)
    timelines = {} if args.timeline else None
    compiles0 = fleet.compile_count()

    cache = workloads.TraceCache(use_disk=not args.no_trace_cache_disk)
    print(f"sweep: {len(points)} cells on a 1/{args.scale} drive "
          f"({cfg.capacity_gb:.1f} GB, SLC cache "
          f"{cfg.slc_cap_pages * cfg.num_planes} pages)")
    group_timings = []
    from repro.telemetry import profiling
    with (tracer.activate() if tracer else contextlib.nullcontext()):
        with profiling.profile(args.profile):
            results = run_sweep(cfg, points, max_ops=args.max_ops,
                                progress=lambda s: print(f"  {s}"),
                                trace_cache=cache, timings=group_timings,
                                timeline_ops=args.timeline,
                                timelines=timelines)
        overhead = None
        if args.timeline_overhead_check:
            # warm-vs-warm: the main run above compiled the telemetry-on
            # programs; one off-pass compiles the off-programs, then both
            # modes are timed warm — INTERLEAVED off/on pairs, median of
            # 3, because background load drifts on the scale of one
            # sweep pass and sequential one-shot timings alias that
            # drift straight into the ratio
            run_sweep(cfg, points, max_ops=args.max_ops, trace_cache=cache)
            offs, ons = [], []
            for _ in range(3):
                with span("overhead.off-warm", "bench") as rec_off:
                    run_sweep(cfg, points, max_ops=args.max_ops,
                              trace_cache=cache)
                with span("overhead.on-warm", "bench") as rec_on:
                    run_sweep(cfg, points, max_ops=args.max_ops,
                              trace_cache=cache,
                              timeline_ops=args.timeline)
                offs.append(rec_off["dur_s"])
                ons.append(rec_on["dur_s"])
            off_med = sorted(offs)[1]
            on_med = sorted(ons)[1]
            overhead = {
                "off_warm_s": round(off_med, 4),
                "on_warm_s": round(on_med, 4),
                "pairs": 3,
                "ratio": round(on_med / max(off_med, 1e-9), 4)}
            print(f"  timeline overhead: off {overhead['off_warm_s']:.2f}s "
                  f"-> on {overhead['on_warm_s']:.2f}s warm, median of "
                  f"{overhead['pairs']} (ratio {overhead['ratio']:.3f})")
    cstats = cache.stats()
    print(f"  trace cache: {cstats['hits']} hit(s), "
          f"{cstats['misses']} miss(es)")
    disp = sum(g["dispatch_s"] for g in group_timings)
    blk = sum(g["block_s"] for g in group_timings)
    fleet_compiles = fleet.compile_count() - compiles0
    print(f"  async dispatch: {len(group_timings)} group(s), "
          f"{disp:.2f}s dispatching, {blk:.2f}s blocked on results, "
          f"{fleet_compiles} fleet compile(s)")
    tot_ops = sum((g["cells"] + g["pad"]) * g["t_len"]
                  for g in group_timings)
    tot_cells = sum(g["cells"] + g["pad"] for g in group_timings)
    throughput = {
        "ops_per_s": round(tot_ops / max(disp + blk, 1e-9), 1),
        "cells_per_s": round(tot_cells / max(disp + blk, 1e-9), 4),
        "by_group": {f"{g['composition']}/{g['mode']}": {
            "ops_per_s": g["ops_per_s"], "cells_per_s": g["cells_per_s"],
            "t_scan": g["t_scan"], "packed": g["packed"],
            "exec_path": g["exec_path"]}
            for g in group_timings}}
    print(f"  throughput: {throughput['ops_per_s'] / 1e6:.3f} Mops/s, "
          f"{throughput['cells_per_s']:.2f} cells/s")
    print(throughput_table(group_timings))

    _print_table(results)

    n_seeds = len({pt.seed for pt in points})
    payload = {"grid": args.grid or "custom", "n_cells": len(points),
               "max_ops": args.max_ops, "scale": args.scale,
               "trace_cache": cstats,
               "group_timings": group_timings,
               "throughput": throughput,
               "fleet_compiles": fleet_compiles,
               "shard_skipped": fleet.shard_skip_count(),
               "results": results,
               "geomeans": {f"{m}/{p}": v for (m, p), v in
                            policy_geomeans(results).items()}}
    if any("tbw_proj_gb" in v for v in results.values()):
        endur = endurance_summary(results)
        _print_endurance_table(endur)
        payload["endurance"] = {f"{m}/{p}": v for (m, p), v in
                                endur.items()}
    if any("host_hit_rate" in v for v in results.values()):
        hc = hostcache_summary(results)
        _print_hostcache_table(hc)
        payload["hostcache"] = {f"{m}/{p}/{t}": v for (m, p, t), v in
                                hc.items()}
    if args.grid == "sensitivity":
        deltas = sensitivity_deltas(results)
        _print_sensitivity_table(deltas)
        payload["sensitivity"] = {"/".join(k): v
                                  for k, v in deltas.items()}
    if n_seeds > 1:
        cis = policy_geomeans_ci(results)
        _print_ci_table(cis)
        payload["geomeans_ci"] = {f"{m}/{p}": v
                                  for (m, p), v in cis.items()}
    if args.bench:
        print("\nbenchmark: fleet vs looped eval_cell (full matrix) ...")
        bench = bench_fleet_vs_loop(cfg)
        print(f"  loop {bench['loop_wall_s']:.1f}s -> fleet "
              f"{bench['fleet_wall_s']:.1f}s  "
              f"(speedup {bench['speedup']:.2f}x, max rel diff "
              f"{bench['max_rel_diff']:.2e})")
        payload["fleet_vs_loop"] = {k: v for k, v in bench.items()
                                    if k != "results"}
    if args.timeline:
        cells = {pt.key: tmod.series(tl)
                 for pt, tl in sorted(timelines.items(),
                                      key=lambda kv: kv[0].key)}
        _print_cliff_table(cells)
        tl_doc = timeline_payload(
            cells, window_ops=args.timeline, tracer=tracer,
            extra={"grid": args.grid or "custom", "max_ops": args.max_ops,
                   "scale": args.scale, "fleet_compiles": fleet_compiles,
                   "shard_skipped": fleet.shard_skip_count(),
                   "exec_paths": {f"{g['composition']}/{g['mode']}":
                                  g["exec_path"] for g in group_timings},
                   **({"overhead": overhead} if overhead else {})})
        if not args.no_save:
            tl_name = (f"{args.name}_timeline" if args.name
                       else "timeline")
            tl_path = save_bench(tl_name, tl_doc, directory=args.out_dir,
                                 cfg=cfg)
            print(f"wrote {tl_path}")
    if args.chrome_trace:
        print(f"wrote {chrome_trace(tracer.to_json(), args.chrome_trace)}")
    if not args.no_save:
        name = args.name or f"sweep_{args.grid or 'custom'}"
        path = save_bench(name, payload, directory=args.out_dir, cfg=cfg)
        print(f"\nwrote {path}")
    from repro.telemetry import history
    if not args.no_save and not args.no_history:
        # fidelity geomeans flattened to scalars: the history gate treats
        # any drift as a regression (they are bit-identity-backed)
        flat_gm = {f"{k}/{metric}": v[metric]
                   for k, v in payload["geomeans"].items()
                   for metric in ("mean_write_latency_ms", "wa_paper")
                   if metric in v}
        # host-tier ratios are deterministic (fixed specs, fixed traces),
        # so the history gate guards them like the device geomeans
        flat_gm |= {f"hc:{k}/{metric}": v[metric]
                    for k, v in payload.get("hostcache", {}).items()
                    for metric in ("lat_vs_off", "wa_vs_off")
                    if v.get(metric) is not None}
        rec = history.append_record(
            "sweep", f"{args.grid or 'custom'}:scale={args.scale}"
                     f":max_ops={args.max_ops}:seeds={len(seeds)}",
            directory=args.out_dir,
            ops_per_s=throughput["ops_per_s"],
            cells_per_s=throughput["cells_per_s"],
            geomeans=flat_gm, compiles=fleet_compiles,
            shard_skipped=fleet.shard_skip_count(),
            meta={"n_cells": len(points),
                  "timeline": args.timeline,
                  "exec_paths": sorted({g["exec_path"]
                                        for g in group_timings})})
        print(f"history: appended {rec['kind']}:{rec['config']} "
              f"@ {str(rec['git_sha'])[:12]}")
    if args.history_check:
        failures = history.check_regression(
            history.load_history(args.out_dir)["records"])
        if failures:
            for line in failures:
                print(f"REGRESSION {line}", file=sys.stderr)
            return 1
        print("history: no regression vs trailing baseline")
    return 0


def _print_cliff_table(cells) -> None:
    print("\n=== timeline: performance-cliff detection (DESIGN.md §11) ===")
    rows = [(k, s["cliff"]) for k, s in cells.items()
            if s["cliff"]["detected"]]
    if rows:
        print(f"{'cell':<40}{'window':>7}{'ratio':>8}{'steady':>9}"
              f"{'t_ops':>9}{'recov':>8}")
        for key, c in rows:
            recov = ("" if c["recovery_slope"] is None
                     else f"{c['recovery_slope']:>8.3f}")
            print(f"{key:<40}{c['window']:>7}{c['ratio']:>8.2f}"
                  f"{c['steady_lat_ms']:>9.3f}"
                  f"{c['time_to_cliff_ops']:>9}{recov}")
    print(f"  cliffs: {len(rows)}/{len(cells)} cell(s)")


def _run_search(args, cfg, seed: int) -> int:
    """`--search BUDGET`: policy autotuning + scenario search
    (repro.search, DESIGN.md §10) -> BENCH_search.json."""
    from repro import workloads
    from repro.core.ssd import fleet
    from repro.core.ssd.policies import policy_names
    from repro.search import (SCHEDULES, build_space, group_candidates,
                              separation_search, successive_halving)
    from repro.sweep.report import search_front_table, search_rounds_table
    from repro.sweep.store import save_bench

    budget = args.search
    sched = SCHEDULES[budget]
    scen_pair = None
    if args.search_scenario.lower() != "none":
        scen_pair = tuple(args.search_scenario.split(":"))
        unknown = sorted(set(scen_pair) - set(policy_names()))
        if len(scen_pair) != 2 or unknown:
            print(f"error: --search-scenario wants A:B over registered "
                  f"policies, got {args.search_scenario!r}"
                  + (f" (unknown: {','.join(unknown)})" if unknown else ""),
                  file=sys.stderr)
            return 2
    rounds = [dict(r) for r in sched["rounds"]]
    if args.max_ops:                 # CI tightening: cap every round
        for r in rounds:
            r["max_ops"] = (args.max_ops if r["max_ops"] is None
                            else min(r["max_ops"], args.max_ops))
    space = build_space(budget)
    print(f"search[{budget}]: {len(space)} candidate(s) in "
          f"{len(group_candidates(space))} composition group(s), "
          f"{len(rounds)} round(s) on a 1/{args.scale} drive")
    cache = workloads.TraceCache(use_disk=not args.no_trace_cache_disk)
    import contextlib

    from repro.telemetry import Tracer, chrome_trace
    tracer = Tracer() if args.chrome_trace else None
    with (tracer.activate() if tracer else contextlib.nullcontext()):
        tune = successive_halving(
            cfg, space, rounds, seed=seed, keep_frac=sched["keep_frac"],
            min_keep=sched["min_keep"], cell_bucket=sched["cell_bucket"],
            trace_cache=cache, progress=lambda s: print(f"  {s}"))
    doc = tune.to_json()
    if args.chrome_trace:
        print(f"wrote {chrome_trace(tracer.to_json(), args.chrome_trace)}")
    print("\n=== search rounds (survivors / compiles per round) ===")
    print(search_rounds_table(tune.rounds))
    print("\n=== Pareto front: lat/waf/tbw vs declared baselines ===")
    print(search_front_table(doc["front"]))

    scen = None
    if scen_pair is not None:
        pair = scen_pair
        sc = sched["scenario"]
        max_ops = (min(sc["max_ops"], args.max_ops) if args.max_ops
                   else sc["max_ops"])
        print(f"\nscenario search: separate {pair[0]} vs {pair[1]} "
              f"({sc['iters']} iter(s) x {sc['pop']})")
        scen = separation_search(
            cfg, pair[0], pair[1], seed=seed, iters=sc["iters"],
            pop=sc["pop"], max_ops=max_ops,
            progress=lambda s: print(f"  {s}"))
        print(f"  msr geomean {scen['msr_geomean']:.3f} -> found "
              f"{scen['best_ratio']:.3f}: ranking "
              f"{'FLIPS' if scen['flipped'] else 'does not flip'}")

    payload = {"search": budget, "n_candidates": len(space),
               "space": [c.to_json() for c in space],
               "trace_cache": cache.stats(),
               "fleet_compiles": fleet.compile_count(),
               "shard_skipped": fleet.shard_skip_count(),
               **doc}
    if scen is not None:
        payload["scenario_search"] = scen
    if not args.no_save:
        name = args.name or "search"
        path = save_bench(name, payload, directory=args.out_dir, cfg=cfg)
        print(f"\nwrote {path}")
        if not args.no_history:
            from repro.telemetry import history
            total_cells = sum(r.get("cells", 0) for r in doc["rounds"])
            wall = sum(r.get("wall_s", 0.0) for r in doc["rounds"])
            rec = history.append_record(
                "search", f"{budget}:scale={args.scale}"
                          f":max_ops={args.max_ops}",
                directory=args.out_dir,
                cells_per_s=(total_cells / wall if wall else None),
                compiles=fleet.compile_count(),
                shard_skipped=fleet.shard_skip_count(),
                meta={"n_candidates": len(space),
                      "front_size": len(doc["front"])})
            print(f"history: appended {rec['kind']}:{rec['config']} "
                  f"@ {str(rec['git_sha'])[:12]}")
    return 0


def _print_table(results) -> None:
    from repro.sweep.report import normalize_points, policy_geomeans
    lat = normalize_points(results, "mean_write_latency_ms")
    wa = normalize_points(results, "wa_paper")
    if lat:
        print(f"\n{'cell':<40}{'lat/base':>10}{'wa/base':>10}")
        for point in sorted(lat, key=lambda p: p.key):
            print(f"{point.key:<40}{lat[point]:>10.3f}"
                  f"{wa.get(point, float('nan')):>10.3f}")
    print("\n=== geomeans vs declared baseline (paper targets: ips bursty "
          "0.77, ips daily 1.3/0.53, agc daily 0.75/0.59, coop daily "
          "0.78/0.67) ===")
    for (mode, policy), v in sorted(policy_geomeans(results).items()):
        print(f"{mode:>7} {policy:<8} "
              f"lat={v.get('mean_write_latency_ms', float('nan')):.3f} "
              f"wa={v.get('wa_paper', float('nan')):.3f}  (n={v['n']})")


def _print_endurance_table(endur) -> None:
    print("\n=== endurance: lifetime + wear leveling (DESIGN.md §9) ===")
    print(f"{'mode':>7} {'policy':<9}{'tbw/base':>9}{'eol/base':>9}"
          f"{'cyc_max':>9}{'skew':>7}{'eol%':>6}")
    for (mode, policy), v in sorted(endur.items()):
        def fmt(x):
            # "ref": a reference cell (nothing to normalize against);
            # "n/a": a normalized policy with no comparable pairs (e.g.
            # EOL never reached on either side)
            if x is not None:
                return f"{x:.3f}"
            return "ref" if v["is_ref"] else "n/a"
        print(f"{mode:>7} {policy:<9}{fmt(v['tbw_ratio']):>9}"
              f"{fmt(v['eol_ratio']):>9}{v['eff_cycles_max']:>9.1f}"
              f"{v['cycle_skew']:>7.3f}{v['eol_frac']:>6.0%}")


def _print_hostcache_table(hc) -> None:
    print("\n=== host-tier cache: hit rate + device-visible writes "
          "(DESIGN.md §14) ===")
    print(f"{'mode':>7} {'policy':<9}{'hostcache':<22}{'hit':>7}"
          f"{'devw':>7}{'lat/off':>9}{'wa/off':>8}")
    for (mode, policy, tag), v in sorted(hc.items()):
        def fmt(x):
            return f"{x:.3f}" if x is not None else "n/a"
        print(f"{mode:>7} {policy:<9}{tag:<22}"
              f"{v['host_hit_rate']:>7.3f}{v['host_dev_write_frac']:>7.3f}"
              f"{fmt(v['lat_vs_off']):>9}{fmt(v['wa_vs_off']):>8}")


def _print_sensitivity_table(deltas) -> None:
    print("\n=== sensitivity: one-axis swaps around ips "
          "(ratios vs ips) ===")
    print(f"{'axis':<11}{'swap':<29}{'policy':<9}{'mode':<7}"
          f"{'lat':>7}{'wa':>7}")
    for (axis, swap, policy, mode), v in sorted(deltas.items()):
        print(f"{axis:<11}{swap:<29}{policy:<9}{mode:<7}"
              f"{v.get('mean_write_latency_ms', float('nan')):>7.3f}"
              f"{v.get('wa_paper', float('nan')):>7.3f}")


def _print_ci_table(cis) -> None:
    print("\n=== seed-pooled geomeans, 95% bootstrap CI ===")
    for (mode, policy), v in sorted(cis.items()):
        lat = v.get("mean_write_latency_ms")
        wa = v.get("wa_paper")
        def fmt(d):
            return (f"{d['geomean']:.3f} [{d['lo']:.3f},{d['hi']:.3f}]"
                    if d else "n/a")
        print(f"{mode:>7} {policy:<8} lat={fmt(lat)} wa={fmt(wa)}  "
              f"(n={v['n']}, seeds={v['n_seeds']})")


if __name__ == "__main__":
    raise SystemExit(main())
