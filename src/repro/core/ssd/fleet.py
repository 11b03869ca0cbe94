"""Fleet simulation: batched multi-trace / multi-seed SSD simulation.

Generalizes `sim.run_trace` from one `(PAD_OPS,)` trace to a stacked
`(n_cells, PAD_OPS)` trace tensor: all cells of one (composition, mode)
group — traces x seeds x cache sizes x repeat factors — execute inside a
single compiled `vmap(lax.scan)`. Per-cell knobs (`CellParams`) are
traced, so a whole cache-size sweep is one compile; only the policy's
mechanism composition and the mode (which select different code paths)
split compilations (DESIGN.md §4) — two registered policy names with the
same composition share one compiled fleet.

Device sharding: when the process has more than one JAX device (e.g. the
sweep CLI forces `--xla_force_host_platform_device_count=<n>` host devices,
or real accelerators are present), `shard_cells` lays the cell axis across
the device mesh and the jitted fleet scan runs cells in parallel — the scan
carries no cross-cell dependency, so SPMD partitioning is embarrassingly
clean. On one device it degrades to a plain vmap.

Memory: the scan carry (dominated by the per-cell residency map `loc` /
`loc_ep`, ~192 KB per cell at the 2^16 logical window) is built outside
the jit and DONATED (`donate_argnums`), so XLA may alias the initial-state
buffers into the scan instead of holding both across the fleet — the peak
saving scales with the cell count.

Equivalence contract: `run_fleet(...)[i]` is bit-for-bit identical to
`run_trace` on cell i with the same `CellParams` (verified by
tests/test_fleet.py). `driver.eval_cell` remains the single-cell reference
implementation.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ssd.config import SSDConfig
from repro.core.ssd.policies import resolve_spec, tracked_region
from repro.core.ssd.policies.engine import _build_core, reduced_of
from repro.core.ssd.policies.state import can_pack
from repro.core.ssd.sim import (CellParams, SimState, flush_cache,
                                init_state, make_step, replay_pads,
                                replay_pads_windowed, summarize)
from repro.telemetry import spans
from repro.workloads.compress import TRIM_QUANTUM

__all__ = ["stack_params", "stack_ops", "shard_cells", "init_fleet_state",
           "FleetPlan", "plan_fleet", "run_fleet", "flush_fleet",
           "summarize_fleet", "compile_count", "cell_quantum",
           "shard_skip_count"]

# cumulative count of shard_cells calls that fell back to one device
# because the cell axis did not divide the mesh — a structured signal
# (surfaced in BENCH run metadata and history records) instead of a
# transient stderr warning that scrolls away in long sweeps
_SHARD_SKIPS = 0


def shard_skip_count() -> int:
    """How many fleets ran unsharded this process (cell axis did not
    divide the device count). Nonzero means idle devices: pad the cell
    axis to a `cell_quantum()` multiple."""
    return _SHARD_SKIPS


def stack_params(params: Sequence[CellParams]) -> CellParams:
    """Stack per-cell CellParams into one CellParams of (C,) arrays."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params)


def stack_ops(traces: Sequence[dict]) -> dict:
    """Stack padded traces into (C, T) op tensors.

    All traces must share one padded length (workloads pads to a multiple
    of PAD_OPS; `repro.sweep.runner` groups cells by padded length)."""
    lens = {len(t["arrival_ms"]) for t in traces}
    if len(lens) != 1:
        raise ValueError(f"traces must share a padded length, got {lens}")
    return {
        "arrival_ms": jnp.asarray(
            np.stack([np.asarray(t["arrival_ms"], np.float32)
                      for t in traces])),
        "lba": jnp.asarray(
            np.stack([np.asarray(t["lba"], np.int32) for t in traces])),
        "is_write": jnp.asarray(
            np.stack([np.asarray(t["is_write"], np.int32)
                      for t in traces])),
    }


def shard_cells(tree, devices=None):
    """Lay the leading (cell) axis of every leaf across the device mesh.

    No-op on a single device or when the cell count does not divide the
    device count (XLA would have to pad; callers pad cells instead when
    they care — see sweep.runner)."""
    devices = jax.devices() if devices is None else list(devices)
    n_dev = len(devices)
    leaves = jax.tree.leaves(tree)
    if n_dev <= 1 or not leaves:
        return tree
    n_cells = leaves[0].shape[0]
    if n_cells % n_dev != 0:
        # the silent path here cost real debugging time: a fleet that
        # falls back to one device looks merely "slow" — count it
        # (shard_skip_count feeds BENCH metadata + history records)
        global _SHARD_SKIPS
        _SHARD_SKIPS += 1
        spans.event("fleet.shard_skipped", "fleet", n_cells=n_cells,
                    n_devices=n_dev, idle_devices=n_dev - 1)
        return tree
    mesh = jax.sharding.Mesh(np.array(devices), ("cells",))
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("cells"))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def init_fleet_state(cfg: SSDConfig, n_logical: int, n_cells: int, *,
                     endurance: bool = False, timeline=None,
                     packed: bool = False, hostcache=None) -> SimState:
    """(C,)-stacked initial SimState (the donated fleet scan carry).
    `timeline` — ops per telemetry window, or None — attaches the
    per-cell in-scan probe (DESIGN.md §11). `packed` carries the integer
    plane fields int16 (gate on `policies.state.can_pack`; results are
    bit-identical, the donated carry just shrinks — DESIGN.md §12).
    `hostcache` — a `HostCacheSpec`, or None — attaches the per-cell
    host-tier cache carry (DESIGN.md §14). The carry dtypes key
    `_run_fleet`'s jit, so packing needs no static flag of its own."""
    return jax.vmap(
        lambda _: init_state(cfg, n_logical, endurance=endurance,
                             timeline=timeline, packed=packed,
                             hostcache=hostcache))(
        jnp.arange(n_cells))


def _cell_step(cfg: SSDConfig, spec, hostcache, *, closed_loop: bool,
               params: CellParams):
    """One cell's scan step: the host tier's pipeline in front of the
    device when `hostcache` is given (DESIGN.md §14), else the device
    step itself."""
    if hostcache is None:
        return make_step(cfg, spec, closed_loop=closed_loop, params=params)
    from repro.hostcache.pipeline import build_tier_step
    return build_tier_step(cfg, spec, hostcache, closed_loop=closed_loop,
                           params=params)


@functools.partial(jax.jit, static_argnames=("cfg", "spec", "closed_loop",
                                             "timeline_ops", "hostcache"),
                   donate_argnums=(2,))
def _run_fleet(cfg: SSDConfig, spec, state0: SimState, ops: dict,
               params: CellParams, *, closed_loop: bool,
               timeline_ops: int | None = None, hostcache=None):
    endurance = params.endurance is not None

    def one(cell_state, cell_ops, cell_params):
        step = _cell_step(cfg, spec, hostcache, closed_loop=closed_loop,
                          params=cell_params)
        if timeline_ops is None:
            final, latency = jax.lax.scan(step, cell_state, cell_ops)
            return latency, final
        from repro.telemetry import probe
        if hostcache is not None:
            from repro.hostcache.model import host_windows
            final, (latency, rows, hrows) = jax.lax.scan(
                step, cell_state, cell_ops)
            wtl = probe.windowed(rows, latency, cell_ops["is_write"],
                                 cell_ops["arrival_ms"],
                                 window_ops=timeline_ops,
                                 t_len=cell_ops["lba"].shape[0],
                                 endurance=False)
            hw = host_windows(hrows, window_ops=timeline_ops,
                              t_len=cell_ops["lba"].shape[0])
            return latency, final._replace(
                timeline=wtl,
                hostcache=final.hostcache._replace(hwin=hw))
        final, (latency, rows) = jax.lax.scan(step, cell_state, cell_ops)
        wtl = probe.windowed(rows, latency, cell_ops["is_write"],
                             cell_ops["arrival_ms"],
                             window_ops=timeline_ops,
                             t_len=cell_ops["lba"].shape[0],
                             endurance=endurance)
        return latency, final._replace(timeline=wtl)

    latency, final = jax.vmap(one)(state0, ops, params)
    return latency, final


def cell_quantum(cell_bucket: int | None = None) -> int:
    """Cell-axis padding quantum: the device count (so `shard_cells` can
    lay the axis across the mesh), lcm'd with `cell_bucket` when given so
    padded cell counts — and hence compiled (C, T) shapes — stay stable
    across runs whose cell counts drift within a bucket (the search
    engine's compile-free knob-refinement contract). Callers pad to a
    multiple of this, replaying the last real cell, and drop the pad from
    results (sweep.runner / search.scenario)."""
    n_dev = len(jax.devices())
    return math.lcm(cell_bucket, n_dev) if cell_bucket else n_dev


@functools.partial(jax.jit, static_argnames=("cfg", "spec", "closed_loop",
                                             "n_pad", "timeline_ops",
                                             "hostcache"),
                   donate_argnums=(2,))
def _run_fleet_trim(cfg: SSDConfig, spec, state0: SimState, ops: dict,
                    params: CellParams, pad_t, *, closed_loop: bool,
                    n_pad: int, timeline_ops: int | None = None,
                    hostcache=None):
    """The trimmed fleet scan: `ops` hold only the (C, T_trim) prefix;
    the `n_pad` identical tail pads every cell shares are re-applied to
    their exact fixed point by `sim.replay_pads` (vmapped — cells
    converge independently, the batched while_loop holds finished cells
    in place). Latency for the tail is literal zeros, appended by the
    caller outside the jit.

    A host-tier fleet (`hostcache`, DESIGN.md §14): a trace pad is a
    fixed point of the tier, except that the watermark latch may clear
    on the first pad. So that pad runs as one tier step, and the
    remaining K·(n_pad − 1) device sub-ops go to `sim.replay_pads`.

    `timeline_ops` (static, plain fleets only) keeps telemetry on the
    trimmed fast path (DESIGN.md §13): the probe rows cover the scanned
    prefix, the replayed tail snapshots counters at the remaining window
    boundaries (`sim.replay_pads_windowed`), and `probe.windowed_prefix`
    assembles the same per-window series the full-length scan produces —
    bit-identical window for window, for any window size."""
    def one(cell_state, cell_ops, cell_params, cell_pad_t):
        step = _cell_step(cfg, spec, hostcache, closed_loop=closed_loop,
                          params=cell_params)
        core = _build_core(cfg, spec, closed_loop=closed_loop,
                           params=cell_params)
        if timeline_ops is None:
            final, latency = jax.lax.scan(step, cell_state, cell_ops)
            n_replay = n_pad
            if hostcache is not None:
                final, _ = step(final, {"arrival_ms": cell_pad_t,
                                        "lba": jnp.int32(0),
                                        "is_write": jnp.int32(-1)})
                n_replay = (2 + hostcache.flush_per_op) * (n_pad - 1)
            red = replay_pads(core, reduced_of(final), final.loc[0],
                              final.loc_ep[0], cell_pad_t, n_replay)
            wtl = None
        else:
            from repro.telemetry import probe
            t_scan = cell_ops["lba"].shape[0]
            t_len = t_scan + n_pad
            final, (latency, (head, ctr_rows)) = jax.lax.scan(
                step, cell_state, cell_ops)
            _, counts = probe.tail_windows(t_len, t_scan, timeline_ops)
            red, tail_ctr = replay_pads_windowed(
                core, reduced_of(final), final.loc[0], final.loc_ep[0],
                cell_pad_t, counts)
            # rebuild the full-length op arrays from the pad contract
            # (latency 0.0, is_write -1, arrival = pad_t)
            wtl = probe.windowed_prefix(
                head, ctr_rows, tail_ctr,
                jnp.concatenate([latency, jnp.zeros(n_pad, jnp.float32)]),
                jnp.concatenate([cell_ops["is_write"],
                                 jnp.full((n_pad,), -1, jnp.int32)]),
                jnp.concatenate([cell_ops["arrival_ms"],
                                 jnp.full((n_pad,), cell_pad_t,
                                          jnp.float32)]),
                window_ops=timeline_ops, t_len=t_len, t_scan=t_scan)
        return latency, final._replace(timeline=wtl, **red._asdict())

    return jax.vmap(one)(state0, ops, params, pad_t)


def compile_count() -> int:
    """Fleet-scan compilations so far in this process: the sizes of the
    `_run_fleet` and `_run_fleet_trim` jit caches, keyed on (cfg,
    composition, mode, host tier) and the stacked (C, T) array shapes —
    including the carry dtypes, so packed and unpacked fleets compile
    separately. Traced-knob variation (CellParams values, endurance
    weights/budgets) never grows it. The search engine (repro.search)
    records per-round deltas of this in BENCH_search.json and asserts
    knob-only rounds add zero."""
    return _run_fleet._cache_size() + _run_fleet_trim._cache_size()


def _trim_len(is_write: np.ndarray, quantum: int = TRIM_QUANTUM) -> int:
    """Shared scannable prefix of a stacked (C, T) fleet: the largest
    per-cell live count, rounded up to `quantum` so drifting live counts
    share compiled shapes. Beyond it every cell holds only its identical
    tail pads (`ir.pad_ops` appends pads tail-only)."""
    live = is_write >= 0
    t_len = is_write.shape[1]
    any_live = live.any(axis=1)
    last = t_len - np.argmax(live[:, ::-1], axis=1)
    n_live = int(np.max(np.where(any_live, last, 0), initial=1))
    return min(-(-n_live // quantum) * quantum, t_len)


class FleetPlan(NamedTuple):
    """The regime a fleet runs (DESIGN.md §12): it scans `t_scan` of its
    steps — fewer than all means the trimmed program replays the pad
    tail — and carries its integer plane fields int16 when `packed`."""
    t_scan: int
    packed: bool


def plan_fleet(cfg: SSDConfig, n_logical: int, is_write,
               params: CellParams, *, hostcache=None,
               timeline_ops: int | None = None) -> FleetPlan:
    """Decide a fleet's regime from its (C, T) `is_write` and
    (C,)-stacked `params`: trim to the shared live prefix (`_trim_len`)
    unless the fleet tracks wear (tail reclamation keeps erasing into
    the wear state) or is a host tier with a timeline (its host windows
    need a per-op row to the end); pack when every cell passes
    `policies.state.can_pack` and the fleet has no host tier."""
    t_scan = is_write.shape[1]
    if params.endurance is None and (hostcache is None
                                     or timeline_ops is None):
        t_scan = _trim_len(np.asarray(is_write))
    return FleetPlan(t_scan, hostcache is None
                     and can_pack(cfg, n_logical, params))


def run_fleet(cfg: SSDConfig, policy, ops: dict, params: CellParams,
              *, closed_loop: bool, n_logical: int,
              timeline_ops: int | None = None, hostcache=None,
              plan: FleetPlan | None = None):
    """Simulate a whole (composition, mode) fleet in one compiled scan.

    ops: (C, T) stacked op tensors from `stack_ops`; params: (C,)-stacked
    CellParams; `policy` a registered name or PolicySpec. Returns
    (latency (C, T), final SimState with leading C). The freshly built
    initial state is donated to the scan (see module docstring).
    `timeline_ops` attaches the per-cell telemetry probe (DESIGN.md §11);
    every cell windows identically over the shared padded length, so the
    final state's `timeline` leaves stack along C like any other field.
    `hostcache` (static: a `HostCacheSpec`, or None) stacks the host
    block-cache tier in front of every cell (DESIGN.md §14).

    The fleet runs the regime `plan_fleet` decides: the trimmed program
    when it scans short, else the full-length `_run_fleet`. Pass `plan`
    to run another — the sweep runner passes the one it records, and
    tests pass the full-length or unpacked reference. Results are
    bit-identical in every regime."""
    spec = resolve_spec(policy)
    n_cells, t_len = ops["lba"].shape
    if plan is None:
        plan = plan_fleet(cfg, n_logical, ops["is_write"], params,
                          hostcache=hostcache, timeline_ops=timeline_ops)
    state0 = shard_cells(init_fleet_state(
        cfg, n_logical, n_cells, endurance=params.endurance is not None,
        timeline=timeline_ops, packed=plan.packed, hostcache=hostcache))
    if plan.t_scan == t_len:
        return _run_fleet(cfg, spec, state0, ops, params,
                          closed_loop=closed_loop, timeline_ops=timeline_ops,
                          hostcache=hostcache)
    n_pad = t_len - plan.t_scan
    ops_trim = {k: v[:, :plan.t_scan] for k, v in ops.items()}
    pad_t = jnp.asarray(ops["arrival_ms"][:, plan.t_scan], jnp.float32)
    latency, final = _run_fleet_trim(
        cfg, spec, state0, ops_trim, params, pad_t, closed_loop=closed_loop,
        n_pad=n_pad, timeline_ops=timeline_ops, hostcache=hostcache)
    return jnp.pad(latency, ((0, 0), (0, n_pad))), final


def flush_fleet(cfg: SSDConfig, states: SimState, policy) -> SimState:
    """Vectorized end-of-workload flush (sim.flush_cache) over the C axis."""
    if tracked_region(resolve_spec(policy)) is None:
        return states
    return jax.vmap(lambda s: flush_cache(cfg, s, policy))(states)


def summarize_fleet(latency, is_write, states: SimState, *,
                    params: CellParams | None = None,
                    cfg: SSDConfig | None = None) -> dict:
    """Per-cell summaries: dict of (C,) arrays (same keys as sim.summarize).

    is_write: (C, T) int array (padding < 0 is excluded by the == 1 test
    inside summarize). Pass the (C,)-stacked `params` (+ cfg) to get the
    endurance lifetime metrics for wear-tracked fleets (DESIGN.md §9)."""
    if params is None or params.endurance is None:
        return jax.vmap(
            lambda lat, w, s: summarize(lat, {"is_write": w}, s)
        )(latency, jnp.asarray(is_write), states)
    return jax.vmap(
        lambda lat, w, s, p: summarize(lat, {"is_write": w}, s,
                                       cell=p, cfg=cfg)
    )(latency, jnp.asarray(is_write), states, params)
