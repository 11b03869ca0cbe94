"""Policy engine: assembles the specialized `lax.scan` step from a
mechanism composition (`PolicySpec`).

The engine owns only what every policy shares — idle accounting, op
service/queueing, residency-map maintenance, counters; everything
policy-specific arrives as mechanism fragments selected *statically* from
the spec, so each composition compiles to exactly the code it needs
(XLA never sees the unselected fragments).

Fragment order is fixed and canonical (it is the seed monolith's order):

  1. triggered migrate reclamation        (mechanism == "migrate")
  1b. gated-reprogram fallback migration  (mechanism == "reprogram_gated")
  2. dual-region traditional reclamation  (allocation dual, idle != none)
  3. AGC slot fill                        (idle == "agc")
  4. generation completion                (mechanism == reprogram*)
  5. destination selection + service + bookkeeping (shared)

Endurance tracking (DESIGN.md §9) is orthogonal to the composition: when
`CellParams.endurance` is set (a *static* pytree-structure property, so it
selects its own compiled step), every fragment and the shared section
additionally account P/E events into `SimState.wear`, reads pay the
retention penalty, and the gated mechanism's reliability gate becomes
live. Without it the assembled step is exactly the seed computation.

Step-engine split (DESIGN.md §12): the whole per-op computation lives in
one `_build_core` closure operating on a *reduced* carry (`Reduced`: the
(P,) plane arrays, counters and idle scalars — everything except the
O(n_logical) residency maps) with the op's residency entries handed in
pre-gathered. Two executors share it:

* `build_step` — the seed-identical per-op scan step: gather
  `loc[lba]`/`loc_ep[lba]`, run the core, scatter the results back into
  the full `SimState`. Endurance and the telemetry probe ride here.
* `build_segment_step` — the compressed-segment executor
  (`workloads.compress`): an outer scan over K-op segments whose
  residency gathers/scatters are *vectorized per segment* (the host-side
  segmenter guarantees no lane reads or overwrites a residency entry an
  earlier lane in the same segment wrote), with the core applied lane by
  lane on the reduced carry only. Masked filler lanes (`live=False`)
  write every result back unchanged, so arbitrary segment padding is a
  provable no-op.

Both executors run the same core arithmetic in the same order on the same
values — bit-identity between them is by construction, and enforced by
tests/test_compress.py over every paper composition.

The carry's integer plane fields may arrive packed (int16,
`state.packed_state_dtype`): the core upcasts to int32 at the plane
read and casts back at the write, so packed and unpacked carries are
arithmetic-identical (integer ops are exact; the int16 epoch wraps with
the same mod-2^16 congruence `loc_ep` already uses).

Bit-identity contract: for the four paper compositions the assembled step
executes the monolith's op sequence verbatim — tests/test_policies.py
checks every latency, counter and state field against the vendored golden.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.ssd.endurance.model import (WearState, bucket_cycles,
                                            plane_cycles, trad_cycles)
from repro.core.ssd.policies import idle as idle_mod
from repro.core.ssd.policies import reclaim
from repro.core.ssd.policies.allocation import ALLOCATIONS
from repro.core.ssd.policies.registry import resolve_spec
from repro.core.ssd.policies.spec import (PolicySpec, requires_endurance,
                                          tracked_region)
from repro.core.ssd.policies.state import CTR, CellParams, SimState
from repro.telemetry import probe

__all__ = ["StepCtx", "Reduced", "build_step", "build_segment_step",
           "reduced_of", "state_fields_used", "lane_get", "lane_set",
           "lane_add"]


# Masked lane ops: `x[i]`, `x.at[i].set` and `x.at[i].add` for a traced
# index on a static leading axis, as selects and reductions over the
# one-hot `mask = arange(n) == i` (trailing axes broadcast). Why the step
# core uses them: `_build_core`.

def _lanes(x, mask):
    return mask.reshape(mask.shape + (1,) * (x.ndim - 1))


def lane_get(x, mask):
    """`x[i]`, bit for bit: an integer sum over the one-hot mask (floats
    summed as their bit patterns, so -0.0, inf and NaN survive)."""
    m = _lanes(x, mask)
    if jnp.issubdtype(x.dtype, jnp.floating):
        bits = jnp.dtype(f"int{8 * x.dtype.itemsize}")
        xb = jax.lax.bitcast_convert_type(x, bits)
        return jax.lax.bitcast_convert_type(
            jnp.sum(jnp.where(m, xb, 0), axis=0, dtype=bits), x.dtype)
    return jnp.sum(jnp.where(m, x, 0), axis=0,
                   dtype=jnp.int32).astype(x.dtype)


def lane_set(x, mask, v):
    """`x.at[i].set(v)`."""
    return jnp.where(_lanes(x, mask), jnp.asarray(v).astype(x.dtype), x)


def lane_add(x, mask, v):
    """`x.at[i].add(v)`, in x's dtype (narrow integers wrap alike)."""
    return jnp.where(_lanes(x, mask), x + jnp.asarray(v).astype(x.dtype),
                     x)


class StepCtx:
    """Mutable per-op execution context shared by mechanism fragments.

    Holds the arriving op's predicates, the local plane's state scalars
    (fragments mutate these; the engine writes them back), the running
    counter vector/conflict accumulator, the step's idle budgets, and the
    spec-static cost constants. Plain attributes — everything is a traced
    jax scalar except the Python-level constants."""
    __slots__ = (
        # op predicates
        "is_write", "is_pad",
        # local plane state (mutated by fragments)
        "slc_used", "rp_done", "trad_used", "valid_mig", "epoch_p",
        # accumulators
        "ctr", "conflict",
        # idle budgets (replay mode; 0-filled in closed loop)
        "dev_budget", "full_gap",
        # traced per-cell knobs
        "cap_basic", "cap_trad", "cap_boost", "waste_p",
        # static cost constants
        "c_mig", "c_agc", "c_trad_rp", "erase_ms", "ppb_slc",
        # endurance tracking (DESIGN.md §9): track_wear is a Python bool
        # (False => fragments compile wear-free); the pe_*/erase rows are
        # the local plane's wear, mutated by fragments like plane state;
        # gate_ok is the reliability gate of the gated reprogram mechanism
        "track_wear", "n_buckets", "pe_slc_p", "pe_rp_p", "pe_tlc_p",
        "erase_p", "pe_trad_p", "erase_trad_p", "gate_ok", "fallback_on",
    )


class Reduced(NamedTuple):
    """The step core's carry: `SimState` minus the O(n_logical) residency
    maps (and the optional wear/timeline extensions). This is everything
    the per-op recurrence actually threads sequentially — the segment
    executor scans *only* this, which is what makes hoisting the
    residency traffic out of the sequential loop possible."""
    busy: jnp.ndarray          # (P,) f32
    slc_used: jnp.ndarray      # (P,) i32|i16
    rp_done: jnp.ndarray       # (P,) i32|i16
    trad_used: jnp.ndarray     # (P,) i32|i16
    valid_mig: jnp.ndarray     # (P,) i32|i16
    epoch: jnp.ndarray         # (P,) i32|i16
    counters: jnp.ndarray      # (10,) f32
    prev_t: jnp.ndarray        # () f32
    idle_cum: jnp.ndarray      # () f32
    idle_seen: jnp.ndarray     # (P,) f32


class CoreOut(NamedTuple):
    """Per-op core results beyond the reduced carry: the residency values
    to scatter, the emitted latency, and the observation-only extras the
    telemetry probe consumes (dead code — XLA DCE — when unused)."""
    latency: jnp.ndarray       # () f32 — 0 for pads
    loc_val: jnp.ndarray       # () i8  — residency value for op's lba
    loc_ep_val: jnp.ndarray    # () i16 — epoch stamp for op's lba
    wear: WearState            # updated wear, or None
    occ_delta: jnp.ndarray     # () f32 — cache-resident page delta
    idle_claim: jnp.ndarray    # () f32 — idle budget claimed
    max_cycles: jnp.ndarray    # () f32 — plane cycles (endurance), or None
    ctr: jnp.ndarray           # (10,) f32 — the step's new counter vector


def state_fields_used(spec: PolicySpec):
    """Union of SimState fields the composition's fragments touch, plus
    the fields the engine's shared service/bookkeeping section reads or
    writes for every composition (that shared section touches the whole
    carry — SimState is one fixed pytree — so the union is how mechanism
    declarations are audited, not a pruning oracle). Registry/property
    tests validate the result against `SimState._fields`."""
    fields = {"busy", "slc_used", "rp_done", "trad_used", "valid_mig",
              "epoch", "loc", "loc_ep", "counters", "prev_t", "idle_cum",
              "idle_seen"}
    alloc = ALLOCATIONS[spec.allocation]
    fields.update(alloc.state_fields)
    if spec.mechanism == "migrate":
        fields.update(reclaim.MIGRATE_FIELDS)
    if spec.mechanism == "reprogram":
        fields.update(reclaim.REPROGRAM_FIELDS)
    if spec.mechanism == "reprogram_gated":
        fields.update(reclaim.GATED_FIELDS)
    if alloc.dual and spec.idle != "none":
        fields.update(reclaim.DUAL_RECLAIM_FIELDS)
    if spec.idle == "agc":
        fields.update(idle_mod.AGC_FIELDS)
    if requires_endurance(spec):
        fields.add("wear")
    return frozenset(fields)


def _build_core(cfg, spec: PolicySpec, *, closed_loop: bool,
                params: CellParams):
    """The whole per-op computation as a function of the reduced carry.

    Returns `core(red, op, old, old_ep, wear=None, live=None) ->
    (Reduced, CoreOut)`. `old`/`old_ep` are the op's residency entries,
    pre-gathered by the executor (raw dtypes). `wear` is the full
    WearState when endurance tracking is on. `live` — None for a
    statically real op, or a traced bool lane mask: a dead lane
    (`live == False`) writes every carry leaf and residency value back
    unchanged, making segment padding a provable no-op.

    Plane-indexed state (the `Reduced` plane arrays and the wear rows and
    buckets) is read and written only through `lane_get` / `lane_set` /
    `lane_add` over a one-hot mask of the static axis, never by `[plane]`
    or `.at[plane]`: vmapped over a fleet's cells those become batched
    scatters and gathers, serial fusions on the TPU (a scatter ~0.9 us
    on a v5e), where the masked forms are elementwise ops that fuse with
    their neighbours. The values are the same bit for bit."""
    t_ = cfg.timing
    p_total = cfg.num_planes
    alloc = ALLOCATIONS[spec.allocation]
    dual = alloc.dual
    use_rp = spec.mechanism in ("reprogram", "reprogram_gated")
    gated = spec.mechanism == "reprogram_gated"
    run_migrate = spec.mechanism == "migrate"   # validate_spec guarantees
    #                                             an idle scheduler exists
    run_dual_reclaim = dual and spec.idle != "none"
    run_agc = spec.idle == "agc"
    pressure = spec.trigger == "watermark"
    tracked = tracked_region(spec)
    use_endurance = params.endurance is not None
    endur = params.endurance
    if requires_endurance(spec) and not use_endurance:
        raise ValueError(
            f"{spec.composition} requires endurance tracking: pass "
            "CellParams.endurance (default_cell attaches default "
            "EnduranceSpec knobs for such compositions)")
    wear_aware = alloc.wear_aware
    n_buckets = cfg.wear_buckets
    cap_basic = params.cap_basic
    cap_trad = params.cap_trad
    cap_boost = (jnp.int32(0) if params.cap_boost is None
                 else params.cap_boost)
    waste_p = params.waste_p
    ppb_slc = cfg.pages_per_slc_block

    c_mig = t_.slc_read_ms + t_.tlc_write_ms        # SLC -> TLC migration
    c_agc = t_.tlc_read_ms + t_.reprogram_ms        # AGC fill of used SLC
    c_trad_rp = t_.slc_read_ms + t_.reprogram_ms    # trad SLC -> IPS region
    idle_thr = params.idle_thr

    def core(red: Reduced, op, old_raw, old_ep, wear: WearState = None,
             live=None):
        # live-masking helper: a dead lane keeps the previous value. With
        # live=None (per-op path) no masking code is emitted at all.
        if live is None:
            def sel(new, prev):
                return new
        else:
            def sel(new, prev):
                return jnp.where(live, new, prev)

        t, lba, kind = op["arrival_ms"], op["lba"], op["is_write"]
        plane = lba % p_total
        lanes = jnp.arange(p_total, dtype=jnp.int32)
        at_p = lanes == plane
        # integer plane state may be carried packed (int16) — compute in
        # int32 (exact for both widths) and cast back at the write
        dt_i = red.slc_used.dtype

        def get_p(x):
            return lane_get(x, at_p).astype(jnp.int32)

        ctx = StepCtx()
        ctx.is_pad = kind < 0
        ctx.is_write = kind == 1
        busy_p = lane_get(red.busy, at_p)
        ctx.ctr = red.counters
        slc_used0, trad_used0 = get_p(red.slc_used), get_p(red.trad_used)
        ctx.slc_used, ctx.trad_used = slc_used0, trad_used0
        ctx.rp_done = get_p(red.rp_done)
        ctx.valid_mig = get_p(red.valid_mig)
        ctx.epoch_p = get_p(red.epoch)
        ctx.conflict = jnp.float32(0.0)
        ctx.cap_basic, ctx.cap_trad = cap_basic, cap_trad
        ctx.cap_boost, ctx.waste_p = cap_boost, waste_p
        ctx.c_mig, ctx.c_agc, ctx.c_trad_rp = c_mig, c_agc, c_trad_rp
        ctx.erase_ms, ctx.ppb_slc = t_.erase_ms, ppb_slc
        ctx.track_wear = use_endurance
        if use_endurance:
            ctx.n_buckets = n_buckets
            ctx.pe_slc_p = lane_get(wear.pe_slc, at_p)
            ctx.pe_rp_p = lane_get(wear.pe_rp, at_p)
            ctx.pe_tlc_p = lane_get(wear.pe_tlc, at_p)
            ctx.erase_p = lane_get(wear.erase, at_p)
            ctx.pe_trad_p = lane_get(wear.pe_trad, at_p)
            ctx.erase_trad_p = lane_get(wear.erase_trad, at_p)
            if gated:
                # RARO-style reliability gate: per-page average reprogram
                # count of the plane's region vs the traced budget. The
                # hysteresis band [rp_budget - rp_hysteresis, rp_budget)
                # pre-arms the migrate fallback while conversion is still
                # allowed, so the region is already draining when the gate
                # finally closes (no hard flip at the boundary); with
                # rp_hysteresis == 0 the fallback condition is exactly
                # ~gate_ok — the PR 4 single-threshold gate, bit-identical.
                cap_f = jnp.maximum(cap_basic.astype(jnp.float32), 1.0)
                rp_count = jnp.sum(ctx.pe_rp_p) / cap_f
                ctx.gate_ok = rp_count < endur.rp_budget
                ctx.fallback_on = (rp_count
                                   >= endur.rp_budget - endur.rp_hysteresis)

        # ------------------------------------------------------------
        # 1. idle work on this plane, lazily applied for [busy_p, t)
        # ------------------------------------------------------------
        # Idle accounting (shared by every composition):
        # * Device-level idle: inter-arrival gaps exceeding the threshold
        #   (Turbo-Write semantics) accumulate; every plane can consume the
        #   window in parallel, applied lazily when next touched; unused
        #   past idle expires.
        # * Which fragments consume it — and whether they may overrun into
        #   the arriving write — is the mechanism composition's business
        #   (see module docstring for the canonical order).
        idle_cum = red.idle_cum
        idle_seen_p = lane_get(red.idle_seen, at_p)
        if not closed_loop:
            gap = jnp.maximum(t - red.prev_t, 0.0)
            idle_cum = idle_cum + jnp.where((gap > idle_thr) & ~ctx.is_pad,
                                            gap, 0.0)
            ctx.dev_budget = jnp.where(ctx.is_pad, 0.0,
                                       idle_cum - idle_seen_p)
            ctx.full_gap = jnp.where(ctx.is_pad, 0.0,
                                     jnp.maximum(t - busy_p, 0.0))

            if run_migrate:
                reclaim.migrate_reclaim(ctx, alloc, pressure=pressure)
            if gated:
                reclaim.gated_fallback_reclaim(ctx)
            if run_dual_reclaim:
                reclaim.dual_reclaim(ctx)
            if run_agc:
                idle_mod.agc_fill(ctx, dual=dual, gated=gated)

        # generation completion: fully reprogrammed region -> fresh layer
        if use_rp:
            reclaim.generation_completion(ctx)

        # ------------------------------------------------------------
        # 2. service the op
        # ------------------------------------------------------------
        is_write, is_pad, conflict = ctx.is_write, ctx.is_pad, ctx.conflict
        slc_used, rp_done = ctx.slc_used, ctx.rp_done
        trad_used, valid_mig, epoch_p = (ctx.trad_used, ctx.valid_mig,
                                         ctx.epoch_p)

        if closed_loop:
            wait = jnp.float32(0.0)
            start = busy_p + conflict
        else:
            wait = jnp.maximum(busy_p - t, 0.0)
            start = t + wait + conflict

        old = old_raw.astype(jnp.int32)
        old_clip = jnp.clip(old, 0, p_total - 1)
        at_old = lanes == old_clip
        # epoch may have been bumped this step (erase) for the local plane
        epoch_eff = jnp.where(old_clip == plane, epoch_p,
                              lane_get(red.epoch, at_old).astype(jnp.int32))
        old_ok = (old >= 0) & (old_ep == epoch_eff.astype(jnp.int16))

        # write destination: allocation decides region placement, the
        # reprogram mechanism adds the in-place conversion path
        to_slc = is_write & (slc_used < alloc.eff_cap(ctx))
        if dual:
            to_trad = is_write & ~to_slc & (trad_used < cap_trad)
        else:
            to_trad = jnp.zeros_like(to_slc)
        if use_rp:
            rp_avail = 2 * slc_used - rp_done
            to_rp = is_write & ~to_slc & ~to_trad & (rp_avail > 0)
            if gated:
                # budget-exhausted blocks take no more reprogram stress:
                # the overflow write goes TLC-direct instead
                to_rp = to_rp & ctx.gate_ok
        else:
            to_rp = jnp.zeros_like(to_slc)
        to_tlc = is_write & ~to_slc & ~to_trad & ~to_rp

        prog_t = jnp.where(to_slc | to_trad, t_.slc_write_ms,
                           jnp.where(to_rp, t_.reprogram_ms,
                                     t_.tlc_write_ms))
        # gated regions keep ips's conservative read model: resident data
        # may already be densified (completed generations), so cache hits
        # read at TLC speed — residency tracking exists for migration
        # accounting, and must not hand the gated policy a read-speed
        # advantage its ips baseline does not model
        hit_read_ms = t_.tlc_read_ms if gated else t_.slc_read_ms
        read_t = jnp.where(old_ok, hit_read_ms, t_.tlc_read_ms)
        if use_endurance:
            # retention-derived read cost: aged blocks need read-retry,
            # ramping linearly to read_penalty_ms at the cycle budget
            # (worst of the plane's basic and traditional regions)
            aged = jnp.maximum(
                plane_cycles(ctx.pe_slc_p, ctx.pe_rp_p, ctx.erase_p,
                             endur, cap_basic),
                trad_cycles(ctx.pe_trad_p, ctx.erase_trad_p, endur,
                            cap_trad))
            age = jnp.clip(aged / jnp.maximum(endur.cycle_budget, 1e-9),
                           0.0, 1.0)
            read_t = read_t + endur.read_penalty_ms * age
        service = jnp.where(is_write, prog_t, read_t)
        service = jnp.where(is_pad, 0.0, service)
        latency = jnp.where(is_pad, 0.0,
                            wait + conflict + service)
        busy_new = jnp.where(is_pad, busy_p, start + service)

        # wear accounting (DESIGN.md §9): a basic-region host program
        # lands in a wear bucket — the sequential fill position by
        # default, the coldest bucket under wear-aware allocation;
        # reprogram stress lands at the conversion position. Traditional-
        # region programs are tracked per plane (own blocks/capacity).
        if use_endurance:
            if wear_aware:
                bkt_slc = jnp.argmin(endur.w_slc * ctx.pe_slc_p
                                     + endur.w_rp * ctx.pe_rp_p
                                     ).astype(jnp.int32)
            else:
                bkt_slc = jnp.clip(
                    slc_used * n_buckets // jnp.maximum(cap_basic, 1),
                    0, n_buckets - 1)
            bkt_rp = jnp.clip(
                rp_done * n_buckets // jnp.maximum(2 * slc_used, 1),
                0, n_buckets - 1)

        # bookkeeping
        slc_used += to_slc.astype(jnp.int32)
        trad_used += to_trad.astype(jnp.int32)
        rp_done += to_rp.astype(jnp.int32)

        # residency tracking covers exactly the migratable region (the
        # gated mechanism also tracks reprogrammed data: it must migrate
        # out if the block's budget exhausts; to_rp is identically False
        # for the plain migrate mechanism)
        if tracked == "basic":
            track_new = to_slc | to_rp
        elif tracked == "trad":
            track_new = to_trad
        else:
            track_new = jnp.zeros_like(to_slc)
        # invalidate previous cached copy (only on real writes)
        valid_dec = (is_write & old_ok).astype(jnp.int32)

        ctr = ctx.ctr
        ctr = ctr.at[CTR["host_w"]].add(is_write.astype(jnp.float32))
        ctr = ctr.at[CTR["slc_w"]].add((to_slc | to_trad).astype(jnp.float32))
        ctr = ctr.at[CTR["tlc_w"]].add(to_tlc.astype(jnp.float32))
        ctr = ctr.at[CTR["rp_host"]].add(to_rp.astype(jnp.float32))
        ctr = ctr.at[CTR["conflict_ms"]].add(jnp.where(is_write, conflict,
                                                       0.0))

        # mapping update: writes set the new location; reads/pads keep it
        loc_val = jnp.where(is_write,
                            jnp.where(track_new, plane, -1),
                            old).astype(jnp.int8)
        loc_ep_val = jnp.where(is_write & track_new,
                               epoch_p.astype(jnp.int16), old_ep)

        if use_endurance:
            buckets = jnp.arange(n_buckets, dtype=jnp.int32)
            pe_slc_new = lane_add(ctx.pe_slc_p, buckets == bkt_slc,
                                  jnp.where(to_slc, 1.0, 0.0))
            pe_rp_new = lane_add(ctx.pe_rp_p, buckets == bkt_rp,
                                 jnp.where(to_rp, 1.0, 0.0))
            pe_tlc_new = ctx.pe_tlc_p + jnp.where(to_tlc, 1.0, 0.0)
            pe_trad_new = ctx.pe_trad_p + jnp.where(to_trad, 1.0, 0.0)
            ops_seen = wear.ops_seen + jnp.where(is_pad, 0.0, 1.0)
            max_cycles = jnp.maximum(
                jnp.max(bucket_cycles(pe_slc_new, pe_rp_new, ctx.erase_p,
                                      endur, cap_basic)),
                trad_cycles(pe_trad_new, ctx.erase_trad_p, endur,
                            cap_trad))
            tripped = max_cycles >= endur.cycle_budget

            def set_w(x, new):
                return lane_set(x, at_p, sel(new, lane_get(x, at_p)))

            wear_new = WearState(
                pe_slc=set_w(wear.pe_slc, pe_slc_new),
                pe_rp=set_w(wear.pe_rp, pe_rp_new),
                pe_tlc=set_w(wear.pe_tlc, pe_tlc_new),
                erase=set_w(wear.erase, ctx.erase_p),
                pe_trad=set_w(wear.pe_trad, pe_trad_new),
                erase_trad=set_w(wear.erase_trad, ctx.erase_trad_p),
                ops_seen=sel(ops_seen, wear.ops_seen),
                eol_op=sel(jnp.where((wear.eol_op < 0) & tripped & ~is_pad,
                                     ops_seen, wear.eol_op), wear.eol_op),
            )
        else:
            wear_new = None
            max_cycles = None

        # observation-only extras for the telemetry probe (DESIGN.md §11):
        # dead code under XLA DCE whenever the executor drops them
        occ_delta = ((slc_used + trad_used) - (slc_used0 + trad_used0)
                     ).astype(jnp.float32)
        idle_claim = jnp.where(is_pad, 0.0, idle_cum - idle_seen_p)

        valid_mig_new = lane_set(red.valid_mig, at_p,
                                 sel(valid_mig, ctx.valid_mig).astype(dt_i))
        valid_mig_new = lane_add(valid_mig_new, at_old,
                                 -sel(valid_dec, 0).astype(dt_i))
        valid_mig_new = lane_add(valid_mig_new, at_p,
                                 sel(jnp.where(track_new, 1, 0), 0)
                                 .astype(dt_i))
        new_red = Reduced(
            busy=lane_set(red.busy, at_p,
                          sel(jnp.where(is_pad, busy_p, busy_new), busy_p)),
            slc_used=lane_set(red.slc_used, at_p,
                              sel(slc_used, ctx.slc_used).astype(dt_i)),
            rp_done=lane_set(red.rp_done, at_p,
                             sel(rp_done, ctx.rp_done).astype(dt_i)),
            trad_used=lane_set(red.trad_used, at_p,
                               sel(trad_used, ctx.trad_used).astype(dt_i)),
            valid_mig=valid_mig_new,
            epoch=lane_set(red.epoch, at_p,
                           sel(epoch_p, ctx.epoch_p).astype(dt_i)),
            counters=sel(ctr, red.counters),
            prev_t=sel(jnp.where(is_pad, red.prev_t, t), red.prev_t),
            idle_cum=sel(idle_cum, red.idle_cum),
            idle_seen=lane_set(red.idle_seen, at_p,
                               sel(jnp.where(is_pad, idle_seen_p, idle_cum),
                                   idle_seen_p)),
        )
        out = CoreOut(
            latency=sel(latency, jnp.float32(0.0)),
            loc_val=sel(loc_val, old_raw),
            loc_ep_val=sel(loc_ep_val, old_ep),
            wear=wear_new, occ_delta=occ_delta, idle_claim=idle_claim,
            max_cycles=max_cycles, ctr=ctr)
        return new_red, out

    return core


def reduced_of(state: SimState) -> Reduced:
    """The reduced carry view of a SimState (shared leaves, no copy)."""
    return Reduced(busy=state.busy, slc_used=state.slc_used,
                   rp_done=state.rp_done, trad_used=state.trad_used,
                   valid_mig=state.valid_mig, epoch=state.epoch,
                   counters=state.counters, prev_t=state.prev_t,
                   idle_cum=state.idle_cum, idle_seen=state.idle_seen)


def build_step(cfg, policy, *, closed_loop: bool, params: CellParams):
    """Returns the scan step specialized to (composition, mode).

    `policy` is a registered name or a raw PolicySpec; per-cell knobs
    (cache capacities, boost, idle threshold, waste_p) come from `params`
    as traced scalars."""
    spec = resolve_spec(policy)
    core = _build_core(cfg, spec, closed_loop=closed_loop, params=params)
    p_total = cfg.num_planes
    use_endurance = params.endurance is not None
    cap_basic = params.cap_basic
    cap_trad = params.cap_trad
    cap_boost = (jnp.int32(0) if params.cap_boost is None
                 else params.cap_boost)

    def step(state: SimState, op):
        lba = op["lba"]
        red, out = core(reduced_of(state), op,
                        state.loc[lba], state.loc_ep[lba],
                        wear=state.wear)
        new_state = SimState(
            wear=out.wear,
            busy=red.busy, slc_used=red.slc_used, rp_done=red.rp_done,
            trad_used=red.trad_used, valid_mig=red.valid_mig,
            epoch=red.epoch,
            loc=state.loc.at[lba].set(out.loc_val),
            loc_ep=state.loc_ep.at[lba].set(out.loc_ep_val),
            counters=red.counters, prev_t=red.prev_t,
            idle_cum=red.idle_cum, idle_seen=red.idle_seen,
        )

        # ------------------------------------------------------------
        # 3. telemetry probe (DESIGN.md §11) — observation only: feeds on
        #    values the step already computed and writes nothing but its
        #    own accumulators, so the op sequence above is unchanged.
        #    With the probe on, the step emits (latency, row) through the
        #    scan's output path; `probe.windowed` reduces the rows to
        #    per-window series after the scan.
        # ------------------------------------------------------------
        if state.timeline is not None:
            is_pad = op["is_write"] < 0
            cap_tot = ((cap_basic + cap_boost + cap_trad)
                       .astype(jnp.float32) * p_total)
            tl_new, tl_row = probe.accumulate(
                state.timeline, is_pad=is_pad, counters=out.ctr,
                occ_delta=out.occ_delta, cap_pages=cap_tot,
                idle_claim=out.idle_claim,
                wear=out.max_cycles if use_endurance else None)
            return new_state._replace(timeline=tl_new), (out.latency,
                                                         tl_row)
        return new_state, out.latency

    return step


def build_segment_step(cfg, policy, *, closed_loop: bool,
                       params: CellParams, emit_probe: bool = False):
    """The compressed-segment executor's outer-scan step (DESIGN.md §12).

    Carry: `(Reduced, loc, loc_ep)`. Input: one segment — K consecutive
    trace ops as `(K,)` lane arrays from `workloads.compress`:
    `arrival_ms`/`lba`/`is_write` plus the host-resolved hazard plan
    (`src`: lane index whose residency *output* this lane must consume
    instead of the segment-start gather, -1 when the gather is current;
    `scat_lba`: the lane's lba if it is the segment's final access of
    that lba, else an out-of-range sentinel).

    The O(n_logical) residency traffic — the measured single-cell
    bottleneck — is hoisted out of the sequential recurrence: one
    vectorized gather per segment, the core lane by lane on the reduced
    carry only (intra-segment dependencies resolved through a (K,)
    forwarding buffer per `src`), one vectorized scatter per segment
    (duplicate-free by the `scat_lba` plan, so scatter order cannot
    matter). Every value each lane consumes equals what the per-op step
    would have gathered after its predecessor's scatter — bit-identity
    with `build_step` is by construction. Returns per-lane latencies (K,)
    in trace order.

    Endurance stays a per-op-path concern (the segment executor rejects
    wear carries), but the telemetry probe has a segment-aware form
    (DESIGN.md §13): with `emit_probe` (static) each lane additionally
    emits the core's observation-only `occ_delta`/`idle_claim` scalars
    and the outer step emits the post-segment cumulative counter vector
    — per-segment boundary snapshots `probe.windowed_segments`
    re-expands into the per-op path's exact window series. Off, the
    emitted pytree (and hence the compiled program) is byte-identical
    to PR 8."""
    spec = resolve_spec(policy)
    if params.endurance is not None:
        raise ValueError("segment executor does not carry wear state; "
                         "run endurance cells through the per-op step")
    core = _build_core(cfg, spec, closed_loop=closed_loop, params=params)

    def seg_step(carry, seg):
        red, loc, loc_ep = carry
        lba_k = seg["lba"]                       # (K,) i32
        k = lba_k.shape[0]
        old_k = loc[lba_k]                       # (K,) i8 — one gather
        old_ep_k = loc_ep[lba_k]                 # (K,) i16

        def lane(acc, x):
            red_c, buf_loc, buf_ep = acc
            use_buf = x["src"] >= 0
            s = jnp.clip(x["src"], 0, k - 1)
            old = jnp.where(use_buf, buf_loc[s], x["old"])
            old_ep = jnp.where(use_buf, buf_ep[s], x["old_ep"])
            red_n, out = core(
                red_c,
                {"arrival_ms": x["arrival_ms"], "lba": x["lba"],
                 "is_write": x["is_write"]},
                old, old_ep)
            buf_loc = buf_loc.at[x["lane"]].set(out.loc_val)
            buf_ep = buf_ep.at[x["lane"]].set(out.loc_ep_val)
            emit = (out.latency, out.loc_val, out.loc_ep_val)
            if emit_probe:
                emit += (out.occ_delta, out.idle_claim)
            return (red_n, buf_loc, buf_ep), emit

        (red, _, _), lane_out = jax.lax.scan(
            lane,
            (red, jnp.zeros(k, jnp.int8), jnp.zeros(k, jnp.int16)),
            {"arrival_ms": seg["arrival_ms"], "lba": lba_k,
             "is_write": seg["is_write"], "src": seg["src"],
             "old": old_k, "old_ep": old_ep_k,
             "lane": jnp.arange(k, dtype=jnp.int32)})
        lat_k, locv_k, epv_k = lane_out[:3]
        # one duplicate-free scatter: only each lba's final lane carries
        # its real lba here; superseded lanes hold the sentinel and drop
        loc = loc.at[seg["scat_lba"]].set(locv_k, mode="drop")
        loc_ep = loc_ep.at[seg["scat_lba"]].set(epv_k, mode="drop")
        if emit_probe:
            occ_k, idle_k = lane_out[3:]
            return (red, loc, loc_ep), (lat_k, occ_k, idle_k,
                                        red.counters)
        return (red, loc, loc_ep), lat_k

    return seg_step
