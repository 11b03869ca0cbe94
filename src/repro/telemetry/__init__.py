"""Telemetry engine: in-scan windowed timelines, cliff detection, and
unified span tracing (DESIGN.md §11).

Three layers, separable by dependency weight:

* `spans` — a nested context-manager span tracer (stdlib only). One
  process-wide active tracer (installed via `Tracer.activate()`); every
  instrumented component (`sweep.runner` run/group/dispatch/block,
  `search.tune` rounds, `workloads` parse/build/cache-hit) records into
  it when one is active and degrades to a plain wall-clock measurement
  otherwise, so the legacy BENCH keys (`wall_s`, `group_timings`,
  `dispatch_s`, `block_s`) are now *derived views* over spans. With a
  tracer active the sweep runner also records each fleet's device work
  (`device.scan`, `device.tail`, via `Tracer.record`) and the jaxpr
  traces and backend compiles of each of its phases.
* `probe` — the in-scan probe engine (imports jax; NOT imported by this
  package `__init__`, which stays jax-free so `repro.sweep`'s
  import-before-XLA_FLAGS contract holds). `TimelineState` is an optional
  trailing `SimState` carry field — statically absent when disabled,
  exactly the endurance `wear` pattern — that integrates running
  telemetry inside the `lax.scan` step and emits one narrow row per op
  through the scan's output path; `probe.windowed` reduces the rows to
  per-window series in the same jit, and the final state carries the
  reduced `WindowedTimeline`.
* `timeline` / `export` — numpy-only analysis (per-window series,
  histogram percentiles, cliff detection) and artifact export
  (`BENCH_timeline.json` payloads, Chrome trace-event files loadable in
  `chrome://tracing` / Perfetto).
* `history` — the append-only, git-SHA-keyed perf-regression ledger
  (`BENCH_history.json`, stdlib-only; DESIGN.md §13) every sweep /
  search / bench_step run appends to, gated by
  `python -m repro.telemetry.history --check`.
* `profiling` — opt-in `jax.profiler` capture (jax imported lazily;
  DESIGN.md §13).
"""
from repro.telemetry.export import (chrome_trace, round_floats,
                                    timeline_payload)
from repro.telemetry.spans import Tracer, active_tracer, event, span
from repro.telemetry.timeline import (cell_timeline, detect_cliff,
                                      percentile, series,
                                      timeline_to_numpy)

__all__ = [
    "Tracer", "active_tracer", "span", "event",
    "timeline_to_numpy", "cell_timeline", "series", "detect_cliff",
    "percentile", "timeline_payload", "chrome_trace", "round_floats",
    "append_record", "check_regression", "load_history",
]

_HISTORY_NAMES = ("append_record", "check_regression", "load_history")


def __getattr__(name):
    # history stays un-imported at package import so that
    # `python -m repro.telemetry.history` is not a runpy double-import
    if name in _HISTORY_NAMES:
        from repro.telemetry import history
        return getattr(history, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
