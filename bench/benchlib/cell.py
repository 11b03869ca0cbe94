"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

The program is reached through `Program` alone: its sweep entry
(`repro.sweep.runner.run_sweep`), its trace builder (for the recipe
check), its span tracer and the runner's per-group counters. Tests hand
`run_cell` a `Program` whose timed path is broken, and no chip.
"""
from __future__ import annotations

import dataclasses
import os
import random
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchlib import catalog, check, reference, synth, window, xtrace

TRACED_MARK = "bench.traced"
DISPATCH_SPAN = "sweep.dispatch"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def point_fields(config: dict) -> dict:
    """Keyword arguments that every sweep point of the configuration's
    cells gets: its `point` group, `SweepPoint`'s own fields by name. A
    nested group becomes the spec class of its field (`hostcache` a
    `HostCacheSpec`, `endurance` an `EnduranceSpec`); the dataclasses
    refuse a field they do not have."""
    import typing
    from repro.core.ssd.endurance.spec import EnduranceSpec
    from repro.hostcache.spec import HostCacheSpec
    from repro.sweep.grid import SweepPoint
    hints = typing.get_type_hints(SweepPoint, localns={
        "EnduranceSpec": EnduranceSpec, "HostCacheSpec": HostCacheSpec})
    out = {}
    for name, value in config.get("point", {}).items():
        if isinstance(value, dict):
            specs = [t for t in typing.get_args(hints.get(name))
                     if dataclasses.is_dataclass(t)]
            if not specs:
                raise TypeError(f"point field {name!r} is no spec group")
            value = specs[0](**value)
        out[name] = value
    return out


class Program:
    """The system under test as the benchmark drives it."""

    def __init__(self, cell: catalog.Cell):
        from repro import workloads
        from repro.core.ssd.config import SSDConfig, TimingConfig
        from repro.sweep.grid import SweepPoint
        from repro.sweep.runner import run_sweep
        from repro.telemetry.spans import Tracer
        self._workloads, self._run_sweep = workloads, run_sweep
        self._point, self.Tracer = SweepPoint, Tracer
        d = cell.config["drive"]
        self.cfg = SSDConfig(
            channels=d["channels"], chips_per_channel=d["chips_per_channel"],
            dies_per_chip=d["dies_per_chip"],
            planes_per_die=d["planes_per_die"],
            blocks_per_plane=d["blocks_per_plane"],
            pages_per_block=d["pages_per_block"], page_kb=d["page_kb"],
            slc_cache_gb=d["slc_cache_gb"], coop_ips_gb=d["coop_ips_gb"],
            coop_traditional_gb=d["coop_traditional_gb"],
            slc_density_ratio=d["slc_density_ratio"],
            idle_threshold_ms=d["idle_threshold_ms"],
            timing=TimingConfig(**cell.config["timing_ms"]))
        self.max_ops = cell.traffic.get("max_ops")
        self.fields = point_fields(cell.config)
        self.points(cell.traffic, [0])      # a field it has not, at load

    def points(self, traffic: dict, seeds: List[int]) -> list:
        return [self._point(trace=t, mode=traffic["mode"], policy=p,
                            seed=s, **self.fields)
                for p in traffic["policies"] for t in traffic["traces"]
                for s in seeds]

    def sweep(self, points: list):
        """The timed path: one `run_sweep` with a fresh memory-only trace
        cache, results on the host when it returns."""
        timings: List[dict] = []
        res = self._run_sweep(
            self.cfg, points, max_ops=self.max_ops, timings=timings,
            trace_cache=self._workloads.TraceCache(use_disk=False))
        return res, timings

    def build_ops(self, name: str, mode: str, seed: int,
                  n_logical: int) -> dict:
        return self._workloads.build_ops(
            name, n_logical, mode=mode, seed=seed,
            capacity_pages=self.cfg.total_pages)

    def trace_stats(self, name: str) -> Optional[tuple]:
        from dataclasses import astuple
        st = self._workloads.TRACES.get(name)
        return None if st is None else astuple(st)


def iteration_seeds(traffic: dict, base: int, i: int) -> List[int]:
    k = int(traffic.get("seeds_per_iteration", 1))
    return [base + k * i + j for j in range(k)]


class RecipeMismatch(RuntimeError):
    """The program builds a named trace from another recipe than the
    traffic file's."""


def recipe_check(prog: Program, traffic: dict, drive: reference.Drive,
                 seed: int) -> None:
    """Each trace the program builds equals the benchmark's own build
    from the traffic file's recipe, array for array; an `msr` trace's
    published stats equal the recipe's besides."""
    for name, recipe in traffic["traces"].items():
        if recipe["kind"] == "msr":
            have = prog.trace_stats(name)
            want = synth.stats_tuple(recipe["stats"])
            if have != want:
                raise RecipeMismatch(
                    f"trace {name}: the program's stats {have} differ from "
                    f"the traffic file's {want}")
        mine = synth.build(name, recipe, drive.n_logical, drive.total_pages,
                           traffic["mode"], seed)
        theirs = prog.build_ops(name, traffic["mode"], seed, drive.n_logical)
        for k in ("arrival_ms", "lba", "is_write"):
            a, b = np.asarray(theirs[k]), mine[k]
            if a.shape != b.shape or a.dtype != b.dtype or \
                    not np.array_equal(a, b):
                raise RecipeMismatch(
                    f"trace {name} seed {seed}: the program's {k} differs "
                    f"from the traffic file's recipe")
        if int(theirs["n_ops"]) != mine["n_ops"]:
            raise RecipeMismatch(f"trace {name}: n_ops differs")


class MetricMissing(RuntimeError):
    """A traced run found nothing to read for a per-layer metric that
    `BENCHMARK.json` lists for its cell."""


class CompileMeter:
    """Backend compiles (persistent-cache loads included) and jaxpr
    traces, from `jax.monitoring`."""

    def __init__(self):
        import jax
        self.compiles, self.compile_s, self.traces = 0, 0.0, 0
        self.hits = self.misses = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snap(self) -> tuple:
        return self.compiles, self.compile_s, self.traces


SPLIT_SPANS = ("sweep.group", "sweep.dispatch", "sweep.block",
               "device.scan", "device.tail")


def iteration_split(win: window.Window, spans: List[dict],
                    tracer_t0: float) -> List[str]:
    """One line per iteration of the window: its wall, the summed time
    of the runner's spans that start inside it, and each fleet's scan."""
    out = []
    for it in win.iterations:
        a, b = it.t0 - tracer_t0, it.t1 - tracer_t0
        inside = [sp for sp in spans if a <= sp["t0_s"] < b]
        tot = dict.fromkeys(SPLIT_SPANS, 0.0)
        for sp in inside:
            if sp["name"] in tot:
                tot[sp["name"]] += sp["dur_s"]
        scans = [f"{sp['dur_s']:.3f}" for sp in inside
                 if sp["name"] == "device.scan"]
        out.append(f"iteration {it.index}: {b - a:.3f} s; " + " ".join(
            f"{k} {v:.3f}" for k, v in tot.items())
            + f"; fleet scans {' '.join(scans)}")
    return out


@dataclasses.dataclass
class Run:
    """What the per-layer readers read (`bench/metrics/*.py`)."""
    cell: catalog.Cell
    window: window.Window
    spans: List[dict]
    device: Optional[xtrace.Reduction]
    # the runner's timings of the fleet whose dispatch was traced
    traced_group: Optional[dict] = None


def sample_cells(win: window.Window, seed: int, n: int):
    """The iteration and the cells the reference checks, drawn from the
    seed: all of one iteration's cells, or `n` of them."""
    rng = random.Random(seed)
    it = win.iterations[rng.randrange(len(win.iterations))]
    pts = [p for p in it.points if p in it.results]
    if n < len(pts):
        longest = max(pts, key=lambda p: it.results[p]["n_ops"])
        rest = [p for p in pts if p is not longest]
        pts = [longest] + rng.sample(rest, n - 1)
    return pts, [it.results[p] for p in pts]


def reference_results(cell: catalog.Cell, points: list,
                      ftype: str = "float32", device=None) -> List[Dict]:
    """The summaries of `points` by the cell's plain reference
    (`catalog.reference`)."""
    return catalog.reference(cell)(cell.config, cell.traffic, points,
                                   ftype, device)


def memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:       # a backend without memory statistics
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class SliceTrace:
    """A profiler trace of the first `seconds` of one iteration's first
    fleet dispatch.

    A whole iteration runs millions of device operations, more than the
    profiler's buffers hold (they fill within one fleet) and more than a
    run can collect in its time. So the trace starts when the program
    opens its first `sweep.dispatch` span and stops `seconds` later, from
    a thread of its own; that thread's `bench.traced` annotation is the
    traced window. `tracer()` gives the span tracer that starts it."""

    def __init__(self, prog: "Program", trace_dir: str, seconds: float):
        self.prog, self.dir, self.seconds = prog, trace_dir, seconds
        self.thread: Optional[threading.Thread] = None
        self.t_mark: Optional[float] = None
        self.error: Optional[BaseException] = None

    def tracer(self):
        slice_ = self

        class Hooked(self.prog.Tracer):
            def span(self, name, cat="", **args):
                if name == DISPATCH_SPAN and slice_.thread is None:
                    slice_.start()
                return super().span(name, cat, **args)
        return Hooked()

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.thread = threading.Thread(target=self._close, daemon=True)
        self.thread.start()

    def _close(self) -> None:
        import jax
        try:
            with jax.profiler.TraceAnnotation(TRACED_MARK):
                self.t_mark = time.perf_counter()
                time.sleep(self.seconds)
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception as e:      # read back in `join`
                self.error = e

    def join(self) -> None:
        if self.thread is not None:
            self.thread.join()
        if self.error is not None:
            raise self.error

    def file(self) -> Optional[str]:
        files = [os.path.join(base, n)
                 for base, _, names in os.walk(self.dir) for n in names
                 if n.endswith(".xplane.pb")]
        return sorted(files)[-1] if files else None


def host_spans_on_trace(spans, tracer_t0, t_mark, evs) -> list:
    """The traced iteration's program spans on the trace's clock,
    (name, start_ns, end_ns, depth), aligned by the `bench.traced` mark
    (perf_counter `t_mark` on the host)."""
    marks = [e for e in evs if e.name == TRACED_MARK]
    if not marks or t_mark is None:
        return []
    off = marks[0].start_ns - t_mark * 1e9
    out = []
    for sp in spans:
        if sp["dur_s"] <= 0:
            continue
        s = (tracer_t0 + sp["t0_s"]) * 1e9 + off
        out.append((sp["name"], s, s + sp["dur_s"] * 1e9, sp["depth"]))
    return out


def traced_run(cell: catalog.Cell, prog: "Program", win: window.Window,
               spans: List[dict], seed: int, dev: dict) -> tuple:
    """One more iteration after the window, its first fleet dispatch
    traced (`SliceTrace`); returns the readers' `Run` and the breakdown.
    The per-layer readers read the window and its `spans` for host
    metrics and this trace for device metrics."""
    trace_dir = os.path.join(cell.bench_dir, ".trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    slice_ = SliceTrace(prog, trace_dir,
                        float(cell.traffic["trace_seconds"]))
    tracer_t0 = time.perf_counter()
    tracer = slice_.tracer()
    i = len(win.iterations)
    try:
        with tracer.activate():
            _, timings = prog.sweep(prog.points(
                cell.traffic, iteration_seeds(cell.traffic, seed, i)))
        t_iter = time.perf_counter()
    finally:
        slice_.join()
    t_join = time.perf_counter()
    path = slice_.file()
    evs = xtrace.events(path, keep_host=(TRACED_MARK,)) if path else []
    t_read = time.perf_counter()
    shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"trace: {1 if path else 0} file(s); " + xtrace.describe(evs))
    log(f"trace: iteration {t_iter - tracer_t0:.3f} s, then stop "
        f"{t_join - t_iter:.3f} s, read {t_read - t_join:.3f} s")
    red, breakdown = None, None
    if any(xtrace.is_device_plane(e.plane) for e in evs):
        red = xtrace.reduce(evs, window_mark=TRACED_MARK)
        named = xtrace.name_gaps(red.idle_gaps, host_spans_on_trace(
            tracer.spans, tracer_t0, slice_.t_mark, evs))
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        breakdown = {"device_ops": [[n, s] for n, s in red.top_ops],
                     "idle_gaps": [[n, s] for n, s in named]}
        log(f"trace: window {red.window_s:.6f} s, busy {red.busy_s:.6f} s,"
            f" loop period {red.loop_period_s} s")
    group = timings[0] if timings else None
    return Run(cell, win, spans, red, group), breakdown


def run_cell(cell: catalog.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, program: Optional[Program] = None,
             device: Optional[dict] = None) -> dict:
    """One run of the cell; returns the result object (the last line)."""
    traffic = cell.traffic
    drive = reference.drive_of(cell.config)
    meter = CompileMeter()
    prog = program or Program(cell)
    t_init = time.perf_counter()

    warm = [int(w) for w in traffic.get("warmup_seeds", [0])]
    recipe_check(prog, traffic, drive, warm[0])
    t_recipe = time.perf_counter()
    c0 = meter.snap()
    for w in warm:
        prog.sweep(prog.points(traffic, iteration_seeds(traffic, w, 0)))
    c1 = meter.snap()
    t_warm = time.perf_counter()
    log(f"setup split: import_init_s {t_init - t_start:.3f} recipe_check_s "
        f"{t_recipe - t_init:.3f} warmup_s {t_warm - t_recipe:.3f} "
        f"(compile_load_s {c1[1] - c0[1]:.3f}) warmup_iterations "
        f"{len(warm)}")
    log(f"setup compiles: {c1[0] - c0[0]} backend compiles, {c1[2] - c0[2]}"
        f" jaxpr traces; persistent cache {meter.hits} hits, "
        f"{meter.misses} misses")

    tracer_t0 = time.perf_counter()
    tracer = prog.Tracer()

    setup_s = time.perf_counter() - t_start
    with tracer.activate():
        w0 = meter.snap()
        win = window.run(
            lambda i, pts: prog.sweep(pts), lambda i: prog.points(
                traffic, iteration_seeds(traffic, seed, i)), seconds)
        w1 = meter.snap()
    log(f"window: {len(win.iterations)} iterations, {win.seconds:.3f} s, "
        f"{win.live_ops} live ops; {w1[0] - w0[0]} backend compiles and "
        f"{w1[2] - w0[2]} jaxpr traces inside it")
    for line in iteration_split(win, tracer.spans, tracer_t0):
        log(line)
    peak = memory_peak()
    dev = dict(device or device_info())
    dev["memory_peak_bytes"] = peak
    if trace:
        traced, breakdown = traced_run(cell, prog, win, tracer.spans,
                                       seed, dev)

    # the comparison, after the window and the memory reading
    t_ref = time.perf_counter()
    pts, got = sample_cells(win, seed, int(traffic["reference_cells"]))
    want = reference_results(cell, pts)
    cmp = check.compare(got, want, [p.key for p in pts])
    readings = {"cells_missing": win.missing,
                "counter_mismatch": cmp.counter_mismatch,
                "float_rel_gap": cmp.float_rel_gap}
    limits = traffic["limits"]
    correct = check.verdict(readings, limits)
    log(f"reference: {len(pts)} cells in {time.perf_counter() - t_ref:.3f} s"
        f"; widest float gap at {cmp.worst or '-'}")
    for note in cmp.notes[:20]:
        log(f"mismatch {note}")

    out = {"correct": correct, "attempted": win.attempted,
           "failed": win.missing + cmp.bad_cells}
    if not trace:
        out["metrics"] = {
            "sim_ops_per_s": {"value": win.rate, "unit": "ops/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        out["metrics"] = catalog.read_metrics(cell, traced)
        missing = [m.name for m in cell.per_layer
                   if m.name not in out["metrics"]]
        if missing:
            raise MetricMissing(
                f"the traced run read nothing for {', '.join(missing)}"
                + ("" if traced.device else
                   " (the trace holds no device events)"))
        out["breakdown"] = breakdown
    out["device"] = dev
    out["checks"] = check.as_json(readings, limits)
    for line in check.lines(readings, limits):
        log(line)
    return out


def main(argv=None, *, t_start: float) -> int:
    """The command line: exits 2, printing no result, without the chips
    the cell asks for; 3 when the program's traces differ from the
    traffic file's recipe; 4 when a traced run reads nothing for one of
    the cell's per-layer metrics."""
    import argparse
    import json
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = catalog.load_cell(args.workload)

    t_args = time.perf_counter()
    import jax
    t_jax = time.perf_counter()
    dev = device_info()
    log(f"setup split: start_s {t_args - t_start:.3f} import_jax_s "
        f"{t_jax - t_args:.3f} backend_init_s "
        f"{time.perf_counter() - t_jax:.3f}")
    if dev["platform"] != "tpu" or dev["count"] != cell.chips:
        log(f"bench: JAX found {dev['count']} {dev['platform']} device(s) "
            f"({dev['kind']}); cell {cell.name} needs {cell.chips} TPU "
            "chip(s)")
        return 2
    from repro import compile_cache
    compile_cache.enable()
    try:
        out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_start=t_start, device=dev)
    except RecipeMismatch as e:
        log(f"bench: {e}")
        return 3
    except MetricMissing as e:
        log(f"bench: {e}")
        return 4
    print(json.dumps(out), flush=True)
    return 0
