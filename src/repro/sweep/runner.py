"""Fleet sweep runner: batch sweep points into compiled fleets.

Points are grouped by everything that forces a fresh XLA compilation —
(mechanism composition, mode, padded trace length). The composition is the
policy's `PolicySpec` from the registry, NOT its name: two registered
policies with identical compositions land in one group and share one
compiled program. Each group becomes ONE `fleet.run_fleet` call: a
`vmap(lax.scan)` over the stacked (C, T) trace tensor with per-cell traced
`CellParams`, sharded across the process's JAX devices.

Dispatch is meant to be async: jax returns futures, so the runner first
dispatches every group back-to-back and only then blocks on results,
group by group, converting to numpy (`max_pending` bounds the window of
live dispatched buffers for memory-constrained hosts). It overlaps less
than that suggests. Every trace is built in the grouping loop
(`sweep.group`), before the first dispatch, while the device holds
nothing. And on a TPU v5e a fleet's `sweep.dispatch` returns only when
its eager flush and summary are about done on the device: the host waits
while it enqueues them behind the scan. So the next fleet's ramp (trace
lookup, params, stacking, transfer, initial state; ~80 ms) also runs with
the device idle: ~8% of a daily iteration in all, per the runner's own
`device.scan` / `device.tail` spans (PERF.md §5). Per-group
dispatch/block wall-clocks are surfaced via the `timings` parameter and
land in `BENCH_*` metadata (sweep.cli).

Traces come from the workload engine (`repro.workloads`): a point's
`trace` spec may be an MSR name, a scenario-generator name or a trace-file
path, all built through the content-addressed compiled-trace cache
(`workloads.TraceCache`) — one build per (spec, seed, mode, repeat) recipe
per process, memoized on disk across runs. Pass `trace_cache=` to inspect
hit/miss counts (the CLI logs them into `BENCH_*` run metadata).

`driver.eval_cell` remains the single-cell reference path; equivalence is
bit-for-bit (tests/test_fleet.py) because both paths run the same
engine-built step with the same traced params.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

from repro import workloads
from repro.core.ssd import fleet
from repro.core.ssd.config import SSDConfig
# driver is the single-cell reference path: share its constants/calibration
# so the fleet and reference paths cannot diverge (no cycle: driver only
# imports repro.sweep.report, and this module is imported lazily by it)
from repro.core.ssd.driver import (LOGICAL_SPACE_CAP, _agc_waste_p,
                                   agc_waste_from_stats)
from repro.core.ssd.endurance.spec import EnduranceSpec
from repro.core.ssd.policies import get_spec, requires_endurance
from repro.core.ssd.policies.state import can_pack
from repro.core.ssd.sim import default_params
from repro.sweep.grid import SweepPoint
from repro.telemetry.spans import active_tracer, span

__all__ = ["run_sweep", "run_matrix", "bench_fleet_vs_loop"]


def _n_logical(cfg: SSDConfig) -> int:
    return min(cfg.total_pages, LOGICAL_SPACE_CAP)


class _JaxCounts:
    """Process-wide counts of jaxpr traces and backend compiles (a
    persistent-cache load counts as a compile), from one `jax.monitoring`
    listener registered on first use."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.traces = self.compiles = 0
        self._registered = False

    def register(self) -> "_JaxCounts":
        if not self._registered:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            self._registered = True
        return self

    def _on_duration(self, event, _secs, **_):
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1


_JAX_COUNTS = _JaxCounts()


@contextlib.contextmanager
def _phase(name: str, counts: Optional[_JaxCounts], **args):
    """A `sweep` span that, when `counts` is given, carries the jaxpr
    traces and backend compiles made inside it (`jaxpr_traces`,
    `backend_compiles`)."""
    with span(name, "sweep", **args) as rec:
        if counts is None:
            yield rec
            return
        t0, c0 = counts.traces, counts.compiles
        yield rec
        rec["args"]["jaxpr_traces"] = counts.traces - t0
        rec["args"]["backend_compiles"] = counts.compiles - c0


class _Stamp:
    """When a watched result was ready on the device (`t`,
    `time.perf_counter()`), set by `_Completions`."""

    def __init__(self):
        self.ready = threading.Event()
        self.t: Optional[float] = None


class _Completions:
    """A daemon thread that waits, in the order they were handed over, for
    results to be ready on the device, and stamps each on the host clock.

    The wait is `jax.block_until_ready`, which releases the interpreter
    lock; the thread drops each result as soon as it is ready, so it
    frees no buffer later than the runner would. A result whose program
    failed is stamped too: the runner meets the failure again where it
    reads the result."""

    def __init__(self):
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="sweep-completions")
        self._thread.start()

    def watch(self, tree) -> _Stamp:
        stamp = _Stamp()
        self._queue.put((tree, stamp))
        return stamp

    def close(self) -> None:
        self._queue.put(None)
        self._thread.join()

    def __enter__(self) -> "_Completions":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _loop(self) -> None:
        import jax
        for tree, stamp in iter(self._queue.get, None):
            with contextlib.suppress(Exception):
                jax.block_until_ready(tree)
            stamp.t = time.perf_counter()
            del tree
            stamp.ready.set()


def _endurance_of(point: SweepPoint):
    """The point's endurance knobs: its own, or defaults when the policy's
    composition requires wear tracking (reliability gate / wear-aware
    placement — DESIGN.md §9), else None."""
    if point.endurance is not None:
        return point.endurance
    if requires_endurance(get_spec(point.policy)):
        return EnduranceSpec()
    return None


def _cell_params(cfg: SSDConfig, point: SweepPoint, waste_p: float):
    """Per-point CellParams: calibrated waste_p unless pinned, cache_frac
    scaling, idle override, cap_boost scaling, endurance knobs — all
    traced, never a recompile."""
    import jax.numpy as jnp
    p = default_params(cfg, point.policy, waste_p,
                       endurance=_endurance_of(point))
    if point.cache_frac != 1.0:
        p = p._replace(
            cap_basic=jnp.int32(max(int(int(p.cap_basic)
                                        * point.cache_frac), 4)),
            cap_trad=jnp.int32(int(int(p.cap_trad) * point.cache_frac)),
            cap_boost=jnp.int32(int(int(p.cap_boost) * point.cache_frac)))
    if point.idle_threshold_ms is not None:
        p = p._replace(idle_thr=jnp.float32(point.idle_threshold_ms))
    if point.cap_boost_frac is not None:
        p = p._replace(
            cap_boost=jnp.int32(int(int(p.cap_boost)
                                    * point.cap_boost_frac)))
    if point.hostcache is not None:
        from repro.hostcache.model import as_hc_params
        p = p._replace(hostcache=as_hc_params(point.hostcache))
    return p


def run_sweep(cfg: SSDConfig, points: Sequence[SweepPoint], *,
              max_ops: Optional[int] = None,
              progress=None,
              trace_cache: Optional[workloads.TraceCache] = None,
              timings: Optional[List[Dict]] = None,
              max_pending: Optional[int] = None,
              cell_bucket: Optional[int] = None,
              timeline_ops: Optional[int] = None,
              timelines: Optional[Dict] = None,
              trim_pads: bool = True,
              packed: bool | str = "auto"
              ) -> Dict[SweepPoint, Dict[str, float]]:
    """Run every sweep point batched; returns {point: metrics}.

    max_ops truncates traces (smoke/CI runs). `progress` is an optional
    callable(str) for per-group status lines. `trace_cache` supplies the
    compiled-trace cache (a fresh one per call otherwise). `timings`, if
    given, is a list the runner appends one dict per compilation group to:
    policies, mode, composition, cells, t_len, dispatch_s, block_s.
    `max_pending` bounds the async-dispatch window: at most that many
    groups' dispatched buffers stay live before the runner drains the
    oldest (None — the default — dispatches every group before blocking;
    set it on memory-constrained hosts with very large grids, where
    group-count x (C, T) op tensors would multiply peak host RAM).
    `cell_bucket` quantizes each group's padded cell count to a multiple
    of the bucket (on top of the device-count multiple): the compiled
    fleet is keyed on the stacked (C, T) shapes, so repeated sweeps whose
    groups land in the same bucket reuse one compilation even when the
    exact cell count drifts — the search engine (repro.search) relies on
    this for compile-free knob-refinement rounds. Padded cells replay the
    last real cell and are dropped from results either way.
    `timeline_ops` attaches the in-scan telemetry probe (DESIGN.md §11)
    to every fleet with that window size; pass a dict as `timelines` to
    receive each point's raw per-window accumulators ({point: numpy
    timeline dict}, feed to `telemetry.timeline.series`). Per-group
    wall-clocks are measured through `telemetry.spans` — install a Tracer
    to collect the sweep's span tree: `sweep.run` around the call;
    `sweep.group`, `sweep.dispatch` and `sweep.block`, each with the
    `jaxpr_traces` and `backend_compiles` made inside it; each fleet's
    `device.scan` and `device.tail`, stamped by a completion watcher
    thread (DESIGN.md §13). A fleet's `sweep.dispatch` and `device.scan`
    name its host tier (`hostcache`, the spec's tag or None) and the
    device sub-op slots a scanned step runs (`sub_ops`; DESIGN.md §14).
    `timings` keeps working without a tracer, and then no thread starts.
    Each timings row also carries `compiles`: how many fresh fleet
    compilations that group's dispatch triggered, `devices`: how many
    devices its stacked cells were laid over, plus the group's
    throughput (`ops_per_s` over the padded length, `cells_per_s`) and
    which raw-speed knobs applied (`t_scan`, `packed`).

    Raw-speed defaults (DESIGN.md §12): `trim_pads=True` scans only each
    group's shared live prefix and replays the identical all-pad tail to
    its exact fixed point — telemetry groups stay on it (segment-aware
    windows, DESIGN.md §13), and host-tier groups without one
    (DESIGN.md §14); endurance groups and host-tier groups with a
    timeline take the full path (a one-line warning marks the endurance
    fallback when a timeline was requested, and each timings row records
    which `exec_path` ran);
    `packed="auto"` carries int16 plane fields
    whenever every cell's caps provably fit (`policies.state.can_pack`),
    `True`/`False` force it. Results are bit-identical either way —
    committed BENCH geomeans are the regression gate."""
    import jax

    n_logical = _n_logical(cfg)
    n_dev = len(jax.devices())
    cache = (trace_cache if trace_cache is not None
             else workloads.TraceCache())
    # with a tracer active, the run also records its device work
    # (`device.scan`, `device.tail`, stamped by a completion watcher) and
    # the jaxpr traces and backend compiles of each host phase
    tracer = active_tracer()
    counts = None if tracer is None else _JAX_COUNTS.register()

    def cell_trace(pt: SweepPoint) -> dict:
        tr = workloads.build_ops(
            pt.trace, n_logical, mode=pt.mode, seed=pt.seed,
            capacity_pages=cfg.total_pages, repeat=pt.repeat, cache=cache)
        if max_ops is not None:
            tr = workloads.truncate_trace(tr, max_ops)
        return tr

    # AGC waste calibration: published stats for MSR names, fitted stats
    # (on the daily variant) for scenario/file specs — one fit per recipe.
    # The daily tensors come through the same TraceCache, so the fit reuses
    # cells the sweep builds anyway (or warm disk entries).
    fitted_waste: Dict[tuple, float] = {}

    def cell_waste(pt: SweepPoint) -> float:
        if pt.waste_p is not None:
            return pt.waste_p
        if get_spec(pt.policy).idle != "agc":
            return 0.0                  # waste_p only drives AGC policies
        if pt.trace in workloads.TRACES:
            return _agc_waste_p(pt.trace)
        key = (pt.trace, pt.seed, pt.repeat)
        if key not in fitted_waste:
            ops = workloads.build_ops(
                pt.trace, n_logical, mode="daily", seed=pt.seed,
                capacity_pages=cfg.total_pages, repeat=pt.repeat,
                cache=cache)
            st = workloads.fit_stats(
                workloads.ir.trace_from_ops(ops, source=pt.trace),
                n_logical, cfg.total_pages)
            fitted_waste[key] = agc_waste_from_stats(st)
        return fitted_waste[key]

    results: Dict[SweepPoint, Dict[str, float]] = {}

    def drain(grp) -> None:
        with _phase("sweep.block", counts, group=grp["names"],
                    mode=grp["mode"]) as rec:
            summ = {k: np.asarray(v) for k, v in grp["summ"].items()}
            if timelines is not None and grp["tl"] is not None:
                from repro.telemetry import timeline as tmod
                tl_np = tmod.timeline_to_numpy(grp["tl"])
                for i, pt in enumerate(grp["pts"]):
                    timelines[pt] = tmod.cell_timeline(tl_np, i)
            if watcher is not None:
                grp["summ_done"].ready.wait()
        block_s = rec["dur_s"]
        if watcher is not None:
            record_device(grp)
        for i, pt in enumerate(grp["pts"]):
            out = {k: float(v[i]) for k, v in summ.items()}
            out["n_ops"] = grp["n_ops"][i]
            results[pt] = out
        if timings is not None:
            wall = max(grp["dispatch_s"] + block_s, 1e-9)
            n_cells_all = len(grp["pts"]) + grp["pad"]
            timings.append({
                "policies": grp["names"], "mode": grp["mode"],
                "composition": grp["spec"].composition,
                "cells": len(grp["pts"]), "pad": grp["pad"],
                "t_len": grp["t_len"], "t_scan": grp["t_scan"],
                "packed": grp["packed"], "exec_path": grp["exec_path"],
                "devices": grp["devices"],
                "dispatch_s": round(grp["dispatch_s"], 4),
                "block_s": round(block_s, 4),
                # ops/s credits the full padded length each cell covers
                # (the compressed path does the same work in less wall),
                # so the trajectory is comparable across PRs and knobs
                "ops_per_s": round(n_cells_all * grp["t_len"] / wall, 1),
                "cells_per_s": round(n_cells_all / wall, 4),
                "compiles": grp["compiles"]})

    def record_device(grp) -> None:
        """The group's device work on the host clock: its scan program
        (pad replay included) from when it could start — the later of
        `run_fleet` returning and the previous fleet's summary being
        ready — to its result being ready; then its tail (latency
        padding, the eager flush and summary) to the summary being
        ready. The tail is an occupancy interval, an upper bound on the
        device's busy time in it. The dispatch ramp before the scan is
        enqueued is left uncovered: the device is mostly idle there."""
        t_scan, t_summ = grp["scan_done"].t, grp["summ_done"].t
        t0 = grp["t_fleet"]
        if grp["after"] is not None:
            t0 = max(t0, grp["after"].t)
        tracer.record("device.scan", "device", t0, t_scan,
                      group=grp["names"], mode=grp["mode"],
                      cells=len(grp["pts"]), pad=grp["pad"],
                      t_scan=grp["t_scan"], t_len=grp["t_len"],
                      exec_path=grp["exec_path"], **grp["tier"])
        tracer.record("device.tail", "device", t_scan, t_summ,
                      group=grp["names"], mode=grp["mode"])

    with span("sweep.run", "sweep", points=len(points)), \
            (contextlib.nullcontext() if tracer is None
             else _Completions()) as watcher:
        # compilation groups: (composition, mode, padded length, endurance
        # presence, host-cache spec) — names with the same PolicySpec share one
        # compiled fleet; wear tracking changes the carry pytree, so
        # endurance-on and -off cells of one composition cannot share a
        # stacked fleet. The host-cache *spec* (not just presence) splits
        # groups: its mode/promote/flush select code paths and sets/ways fix
        # carry shapes (DESIGN.md §14) — only the float knobs are traced.
        groups: Dict[tuple, list] = defaultdict(list)
        with _phase("sweep.group", counts, points=len(points)) as rec:
            for pt in points:
                groups[(get_spec(pt.policy), pt.mode,
                        len(cell_trace(pt)["arrival_ms"]),
                        _endurance_of(pt) is not None,
                        pt.hostcache)].append(pt)
            rec["args"]["groups"] = len(groups)

        # ---- phase 1: dispatch every group (async — results are futures) ----
        pending = []
        after = None            # the previous fleet's summary stamp
        for (spec, mode, _t_len, _endur, _hc), pts in sorted(
                groups.items(), key=lambda kv: (kv[0][0], kv[0][1], kv[0][2],
                                                kv[0][3], str(kv[0][4]))):
            if max_pending is not None and len(pending) >= max_pending:
                drain(pending.pop(0))       # bounded window: free the oldest
            traces = [cell_trace(p) for p in pts]
            params = [_cell_params(cfg, p, cell_waste(p)) for p in pts]
            # pad the cell axis to a device-count multiple so shard_cells can
            # lay it across the mesh — quantized further to `cell_bucket` for
            # shape-stable recompile-free rounds; padded cells replay the last
            # cell and are dropped below.
            n_cells = len(pts)
            pad = (-n_cells) % fleet.cell_quantum(cell_bucket)
            traces += [traces[-1]] * pad
            params += [params[-1]] * pad

            names = ",".join(sorted({p.policy for p in pts}))
            # packing decision is per group (it keys the compiled carry):
            # every cell's caps must provably fit int16
            pack_grp = (packed if isinstance(packed, bool)
                        else all(can_pack(cfg, n_logical, p) for p in params))
            if _hc is not None:
                # the tier pipeline runs unpacked; it trims its pad tail
                # unless the probe is on, whose host windows need a per-op
                # row to the end (DESIGN.md §14)
                pack_grp = False
            trim_grp = (trim_pads and not _endur
                        and (_hc is None or timeline_ops is None))
            if timeline_ops is not None and trim_pads and _endur:
                # the fallback used to be silent — a fleet that quietly
                # forfeits the fast path just looks "slow" (DESIGN.md §13)
                import warnings
                warnings.warn(
                    f"sweep group {names}/{mode}: timeline requested on an "
                    "endurance group — no trimmed fast path for wear "
                    "tracking, falling back to the full per-op scan",
                    RuntimeWarning, stacklevel=2)
            if progress:
                progress(f"fleet {names}/{mode}: {n_cells} cells"
                         f"{f' (+{pad} pad)' if pad else ''} x {_t_len} ops"
                         f" on {n_dev} device(s)")
            # the host tier, if any: its spec's tag, and the device sub-op
            # slots each scanned step runs (DESIGN.md §14)
            tier = {"hostcache": None if _hc is None else _hc.tag,
                    "sub_ops": 1 if _hc is None else 2 + _hc.flush_per_op}
            c0 = fleet.compile_count()
            with _phase("sweep.dispatch", counts, group=names, mode=mode,
                        cells=n_cells, t_len=_t_len, **tier) as rec:
                ops = fleet.shard_cells(fleet.stack_ops(traces))
                stacked = fleet.shard_cells(fleet.stack_params(params))
                t_scan = (fleet._trim_len(np.asarray(ops["is_write"]))
                          if trim_grp else _t_len)
                latency, states = fleet.run_fleet(
                    cfg, spec, ops, stacked,
                    closed_loop=(mode == "bursty"), n_logical=n_logical,
                    timeline_ops=timeline_ops, trim_pads=trim_grp,
                    packed=pack_grp, hostcache=_hc)
                t_fleet = time.perf_counter()
                scan_done = (None if watcher is None else watcher.watch(
                    jax.tree_util.tree_leaves(states)[0]))
                if mode == "daily":
                    states = fleet.flush_fleet(cfg, states, spec)
                summ = fleet.summarize_fleet(latency, ops["is_write"], states,
                                             params=stacked, cfg=cfg)
                summ_done = None if watcher is None else watcher.watch(summ)
                rec["args"]["compiles"] = fleet.compile_count() - c0
            pending.append({"pts": pts, "n_ops": [t["n_ops"] for t in traces],
                            "summ": summ, "names": names, "mode": mode,
                            "spec": spec, "t_len": _t_len, "pad": pad,
                            "t_scan": t_scan, "packed": pack_grp,
                            "tier": tier,
                            "devices": len(ops["lba"].sharding.device_set),
                            "exec_path": ("segment" if t_scan < _t_len
                                          else "per_op"),
                            "dispatch_s": rec["dur_s"],
                            "compiles": rec["args"]["compiles"],
                            "tl": states.timeline, "t_fleet": t_fleet,
                            "scan_done": scan_done, "summ_done": summ_done,
                            "after": after})
            after = summ_done

        # ---- phase 2: block on each group's results, oldest first ----
        for grp in pending:
            drain(grp)
        return results


def run_matrix(cfg: SSDConfig, *,
               policies: Sequence[str] = ("baseline", "ips", "ips_agc"),
               modes: Sequence[str] = ("bursty", "daily"),
               names: Optional[Iterable[str]] = None, seed: int = 0,
               max_ops: Optional[int] = None,
               trace_cache: Optional[workloads.TraceCache] = None
               ) -> Dict[str, Dict]:
    """Fleet-backed evaluation matrix in `driver.eval_matrix` key format
    (`trace/mode/policy`)."""
    names = tuple(names or workloads.TRACE_NAMES)
    points = [SweepPoint(trace=n, mode=m, policy=p, seed=seed)
              for m in modes for n in names for p in policies]
    res = run_sweep(cfg, points, max_ops=max_ops, trace_cache=trace_cache)
    return {f"{pt.trace}/{pt.mode}/{pt.policy}": v for pt, v in res.items()}


def bench_fleet_vs_loop(cfg: SSDConfig, *,
                        policies=("baseline", "ips", "ips_agc"),
                        modes=("bursty", "daily"),
                        names: Optional[Iterable[str]] = None,
                        progress=None) -> Dict:
    """Wall-clock the fleet matrix against the looped `eval_cell` reference
    on identical cells; verifies per-cell metric equivalence.

    Returns a JSON-ready dict (feed to sweep.store.save_bench)."""
    from repro.core.ssd.driver import eval_cell
    names = tuple(names or workloads.TRACE_NAMES)

    # memory-only cache: the published speedup must be hermetic, not a
    # function of whatever the disk cache happens to hold from prior runs
    cache = workloads.TraceCache(use_disk=False)
    with span("bench.fleet", "bench") as rec:
        fleet_res = run_matrix(cfg, policies=policies, modes=modes,
                               names=names, trace_cache=cache)
    fleet_s = rec["dur_s"]

    with span("bench.loop", "bench") as rec:
        loop_res = {}
        for mode in modes:
            for name in names:
                for policy in policies:
                    if progress:
                        progress(f"loop {name}/{mode}/{policy}")
                    loop_res[f"{name}/{mode}/{policy}"] = eval_cell(
                        cfg, name, policy, mode)
    loop_s = rec["dur_s"]

    max_rel = 0.0
    for key, ref in loop_res.items():
        got = fleet_res[key]
        for metric, rv in ref.items():
            rel = abs(got[metric] - rv) / max(abs(rv), 1e-9)
            max_rel = max(max_rel, rel)
    return {
        "n_cells": len(loop_res),
        "policies": list(policies), "modes": list(modes),
        "names": list(names),
        "loop_wall_s": round(loop_s, 3),
        "fleet_wall_s": round(fleet_s, 3),
        "speedup": round(loop_s / max(fleet_s, 1e-9), 3),
        "max_rel_diff": max_rel,
        "trace_cache": cache.stats(),
        "results": fleet_res,
    }
