"""Shared simulator state and traced per-cell parameters.

Moved out of `repro.core.ssd.sim` so mechanism modules (allocation /
reclaim / idle) and the engine can share them without import cycles;
`sim` re-exports everything for backward compatibility.

`SimState` is the union of the state fields every mechanism may use —
one fixed pytree so fleets of *different* policies stack/stagger with
identical carry shapes, and the fleet equivalence contract can compare
states field-by-field across policies. Each mechanism declares the subset
it reads/writes (`state_fields`), validated against `SimState._fields` at
registration (DESIGN.md §8); unused fields cost nothing after XLA DCE.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.ssd.endurance.model import (EnduranceParams, WearState,
                                            as_params, init_wear)
from repro.core.ssd.endurance.spec import EnduranceSpec
from repro.hostcache.model import HCParams, HCState, init_hc
from repro.telemetry.probe import TimelineState, init_timeline

__all__ = ["CellParams", "SimState", "CTR", "init_state", "default_cell",
           "can_pack", "WATERMARK_NUM", "WATERMARK_DEN", "OVERRUN_PAGES",
           "ceil_div"]

# block-granularity reclamation model: pressure watermark + per-op overrun
WATERMARK_NUM, WATERMARK_DEN = 7, 8
OVERRUN_PAGES = 4               # one reclamation batch an arriving write may
#                                 stall behind (paper Fig. 7)


class CellParams(NamedTuple):
    """Per-cell simulation knobs, *traced* through the compiled scan.

    Everything that varies across sweep cells without changing control flow
    lives here, so one compiled (composition, mode) scan serves every cell
    of a parameter sweep — cache-size and idle-threshold sensitivity runs
    (paper Fig. 12) are compile-free (DESIGN.md §4). The mechanism
    composition and mode stay static: they select different code paths.
    """
    cap_basic: jnp.ndarray   # i32 — SLC pages/plane in the basic/IPS region
    cap_trad: jnp.ndarray    # i32 — dual-allocation traditional pages/plane
    idle_thr: jnp.ndarray    # f32 — device-idle gap threshold (ms)
    waste_p: jnp.ndarray     # f32 — AGC early-migration waste probability
    cap_boost: jnp.ndarray = None  # i32 — adaptive allocation: extra SLC
    #                                pages unlocked above the watermark
    #                                (None == 0 for non-adaptive policies)
    endurance: EnduranceParams = None  # traced wear/reliability knobs
    #                                (DESIGN.md §9); None — endurance
    #                                tracking statically absent, keeping
    #                                the seed pytree and golden identity
    hostcache: HCParams = None  # traced host-tier cache knobs
    #                                (DESIGN.md §14); None — host cache
    #                                statically absent, same contract


class SimState(NamedTuple):
    # The five integer plane fields carry i32, or i16 when the state is
    # *packed* (init_state(packed=True), gated by `can_pack`): the engine
    # computes every plane update in i32 and casts back at the scatter,
    # so packed runs are arithmetic-identical — integers are exact in
    # both widths below the i16 bound, and `epoch` (the one unbounded
    # counter) wraps mod 2^16 exactly congruent with the i16 `loc_ep`
    # stamps it is compared against. Packing shrinks the donated fleet
    # carry so more cells fit per device (DESIGN.md §12).
    busy: jnp.ndarray          # (P,) f32 — plane free time
    slc_used: jnp.ndarray      # (P,) i32|i16 — pages in current basic/IPS region
    rp_done: jnp.ndarray       # (P,) i32|i16 — reprogram writes into that region
    trad_used: jnp.ndarray     # (P,) i32|i16 — dual-alloc traditional pages
    valid_mig: jnp.ndarray     # (P,) i32|i16 — valid pages in migratable region
    epoch: jnp.ndarray         # (P,) i32|i16
    loc: jnp.ndarray           # (N,) i8 — plane holding lba in cache, or -1
    loc_ep: jnp.ndarray        # (N,) i16 — epoch at write (wraps; collisions
    #                            astronomically unlikely within a trace)
    counters: jnp.ndarray      # (10,) f32, see CTR
    prev_t: jnp.ndarray        # () f32 — last arrival (device-level idle)
    idle_cum: jnp.ndarray      # () f32 — cumulative usable device idle
    idle_seen: jnp.ndarray     # (P,) f32 — idle_cum consumed per plane
    wear: WearState = None     # per-plane/bucket P/E state (DESIGN.md §9);
    #                            None unless CellParams.endurance is set —
    #                            jax treats None as an empty pytree, so
    #                            non-endurance carries keep the seed shape
    timeline: TimelineState = None  # in-scan telemetry probe carry
    #                            (DESIGN.md §11); None == statically
    #                            absent, same contract as `wear` — the
    #                            probe is observation-only, so enabling
    #                            it never changes latencies or counters.
    #                            run_trace/run_fleet swap in the reduced
    #                            per-window WindowedTimeline post-scan
    hostcache: HCState = None  # host-tier block-cache carry
    #                            (DESIGN.md §14); None == statically
    #                            absent — the off path is the seed device
    #                            scan, bit for bit. Present, the tier
    #                            pipeline serves hits at host latency and
    #                            rewrites misses/evictions/flushes into
    #                            the device op stream in-scan


CTR = {name: i for i, name in enumerate(
    ["host_w", "slc_w", "tlc_w", "rp_host", "rp_agc", "rp_trad",
     "mig_w", "erases", "agc_waste", "conflict_ms"])}


INT16_MAX = 32767


def can_pack(cfg, n_logical: int, params: CellParams) -> bool:
    """True when every integer plane field provably fits int16, so
    `init_state(packed=True)` is exact (host-side check on concrete
    caps; (C,)-stacked params pass when every cell does). Bounds:
    `slc_used <= cap_basic + cap_boost` (allocation cap),
    `rp_done <= 2 * slc_used` (two reprograms per SLC page),
    `trad_used <= cap_trad`, and `valid_mig <= ceil(n_logical / P)` (an
    lba's cached copy always lives on plane `lba % P`, so a plane can
    hold at most that many valid entries). `epoch` needs no bound — it
    wraps congruent with the int16 `loc_ep` stamps."""
    basic, trad = np.asarray(params.cap_basic), np.asarray(params.cap_trad)
    boost = 0 if params.cap_boost is None else np.asarray(params.cap_boost)
    bound = max(int(np.max(2 * (basic + boost))), int(np.max(trad)),
                ceil_div(n_logical, cfg.num_planes))
    return bound <= INT16_MAX


def init_state(cfg, n_logical: int, *, endurance: bool = False,
               timeline=None, packed: bool = False,
               hostcache=None) -> SimState:
    """Fresh scan carry. `timeline` — ops per telemetry window, or
    None — attaches the in-scan probe carry (DESIGN.md §11). `packed`
    carries the integer plane fields as int16 (caller gates on
    `can_pack`); results are bit-identical either way. `hostcache` — a
    `HostCacheSpec`, or None — attaches the host-tier cache carry
    (DESIGN.md §14) sized by the spec's static geometry."""
    p = cfg.num_planes
    dt_i = jnp.int16 if packed else jnp.int32
    return SimState(
        wear=init_wear(cfg) if endurance else None,
        timeline=init_timeline(timeline) if timeline else None,
        hostcache=init_hc(hostcache) if hostcache is not None else None,
        busy=jnp.zeros(p, jnp.float32),
        slc_used=jnp.zeros(p, dt_i),
        rp_done=jnp.zeros(p, dt_i),
        trad_used=jnp.zeros(p, dt_i),
        valid_mig=jnp.zeros(p, dt_i),
        epoch=jnp.zeros(p, dt_i),
        loc=jnp.full(n_logical, -1, jnp.int8),
        loc_ep=jnp.zeros(n_logical, jnp.int16),
        counters=jnp.zeros(len(CTR), jnp.float32),
        prev_t=jnp.float32(0.0),
        idle_cum=jnp.float32(0.0),
        idle_seen=jnp.zeros(p, jnp.float32),
    )


def ceil_div(a, b):
    return (a + b - 1) // b


def default_cell(cfg, spec, waste_p: float = 0.0,
                 endurance: EnduranceSpec | None = None) -> CellParams:
    """CellParams matching the static config for one composition.

    The reference single-cell path and the fleet path share these exact
    values; per-name defaults come from the allocation mechanism.
    `endurance` enables wear tracking (DESIGN.md §9); compositions that
    require it (reliability gate, wear-aware placement) get default
    `EnduranceSpec` knobs even when the caller passes None."""
    from repro.core.ssd.policies.allocation import ALLOCATIONS
    from repro.core.ssd.policies.spec import requires_endurance
    if endurance is None and requires_endurance(spec):
        endurance = EnduranceSpec()
    cap_basic, cap_trad, cap_boost = \
        ALLOCATIONS[spec.allocation].default_caps(cfg)
    return CellParams(
        cap_basic=jnp.int32(cap_basic),
        cap_trad=jnp.int32(cap_trad),
        idle_thr=jnp.float32(cfg.idle_threshold_ms),
        waste_p=jnp.float32(waste_p),
        cap_boost=jnp.int32(cap_boost),
        endurance=None if endurance is None else as_params(endurance),
    )
