"""Opt-in device profiling hooks (DESIGN.md §13).

The span tracer (`telemetry.spans`) sees host wall-clock only — it can
say a dispatch took 3 s, not whether that was compilation, device
execution, or host-side trace building. This module bridges the gap
without making profiling a dependency:

* `profile(trace_dir)` — context manager around `jax.profiler`
  start/stop trace capture. The captured trace (TensorBoard /
  Perfetto-openable) carries the device timeline; paired
  `profile.start` / `profile.stop` span events mark the captured region
  in the host span tree, so a Chrome-trace export of the spans
  (`telemetry.export.chrome_trace`) and the device trace line up by
  wall-clock. A capture that was asked for and cannot start raises: a
  run that silently lost its device trace would pass for a traced one.
* `device_memory_stats()` — best-effort per-device live-memory
  snapshot (`Device.memory_stats()`; empty on backends without it).
* `dispatch_stats()` — process-wide compile/dispatch counters from
  `jax.monitoring`-free sources: the fleet jit-cache sizes and device
  memory, cheap enough to record per dispatch.
* `emit_device_events(tag)` — posts the above as an instant event on
  the active tracer, so span exports interleave host spans with device
  state without any profiler running.

Everything imports jax lazily: the telemetry package root stays
jax-free (`repro.telemetry.__init__` contract).
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional

from repro.telemetry import spans

__all__ = ["profile", "device_memory_stats", "dispatch_stats",
           "emit_device_events"]


@contextlib.contextmanager
def profile(trace_dir: Optional[str]):
    """Capture a `jax.profiler` trace of the enclosed region into
    `trace_dir` (None: no capture). A failure to start or stop the
    capture propagates. Yields True when a capture is running."""
    if trace_dir is None:
        yield False
        return
    import jax
    jax.profiler.start_trace(trace_dir)
    spans.event("profile.start", "profile", trace_dir=trace_dir)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()
        spans.event("profile.stop", "profile", trace_dir=trace_dir)


def device_memory_stats() -> Dict[str, Dict]:
    """{device: memory_stats} for devices that expose it (interpreter /
    some CPU backends return nothing — callers treat absence as 'not
    supported', never as zero)."""
    try:
        import jax
        devices = jax.devices()
    except Exception:
        return {}
    out: Dict[str, Dict] = {}
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats:
            out[str(dev)] = {k: int(v) for k, v in stats.items()
                             if isinstance(v, (int, float))}
    return out


def dispatch_stats() -> Dict:
    """Cheap per-dispatch device-side indicators: fleet jit-cache sizes
    (compile growth between two snapshots = fresh compilations) and
    peak/live device memory where the backend reports it."""
    out: Dict = {}
    try:
        from repro.core.ssd import fleet
        out["fleet_compiles"] = fleet.compile_count()
    except Exception:
        pass
    mem = device_memory_stats()
    if mem:
        out["bytes_in_use"] = sum(m.get("bytes_in_use", 0)
                                  for m in mem.values())
        peak = sum(m.get("peak_bytes_in_use", 0) for m in mem.values())
        if peak:
            out["peak_bytes_in_use"] = peak
    return out


def emit_device_events(tag: str = "") -> Optional[Dict]:
    """Post `dispatch_stats()` as an instant event on the active tracer
    (no-op without one) — Chrome-trace exports then interleave host
    spans with device compile/memory state at that wall-clock point."""
    stats = dispatch_stats()
    return spans.event("device.stats", "profile", tag=tag, **stats)
