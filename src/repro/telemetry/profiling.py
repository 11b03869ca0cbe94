"""Opt-in device profiling (DESIGN.md §13).

The span tracer (`telemetry.spans`) sees the host clock, plus each
fleet's device work as the sweep runner stamps it (`device.scan`,
`device.tail`): whole programs, not the operations inside them.
`profile(trace_dir)` captures the operations: a context manager around
`jax.profiler` start/stop trace capture. The captured trace (TensorBoard
/ Perfetto-openable) carries the device timeline and, through the
tracer's `TraceAnnotation`s, the host spans on the profiler's own clock;
paired `profile.start` / `profile.stop` span events mark the captured
region in the host span tree. A capture that was asked for and cannot
start raises: a run that silently lost its device trace would pass for
a traced one.

jax is imported lazily: the telemetry package root stays jax-free
(`repro.telemetry.__init__` contract).
"""
from __future__ import annotations

import contextlib
from typing import Optional

from repro.telemetry import spans

__all__ = ["profile"]


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
