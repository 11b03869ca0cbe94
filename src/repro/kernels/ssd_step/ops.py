"""Backend dispatch for the fused segment-scan step.

Same contract as the engine's own executor: feed it the (S, K) segment
arrays from `workloads.compress` and a `SimState` (packed or unpacked);
get back `(latency (S, K), (Reduced, loc, loc_ep))`. On TPU this picks
the Pallas kernel, which the v5e compiler refuses today (`dynamic_slice`,
DESIGN.md §12): the call fails rather than running the jnp path instead.
Elsewhere the pure-jnp engine path runs, and `interpret=True` exercises
the kernel body through the Pallas interpreter (the equivalence gate —
slow, for tests only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd_step.kernel import run_segments_kernel
from repro.kernels.ssd_step.ref import run_segments_ref

__all__ = ["run_segments_fused"]


def run_segments_fused(cfg, policy, segs, state0, *, closed_loop, params,
                       use_pallas: bool | None = None,
                       interpret: bool = False):
    """Execute the compressed-segment stream, dispatching by backend."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas or interpret:
        return run_segments_kernel(cfg, policy, segs, state0,
                                   closed_loop=closed_loop, params=params,
                                   interpret=interpret)
    segs_j = {k: jnp.asarray(v) for k, v in segs.items()}
    return run_segments_ref(cfg, policy, segs_j, state0,
                            closed_loop=closed_loop, params=params)
