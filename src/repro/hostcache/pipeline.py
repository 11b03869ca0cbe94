"""TierPipeline: the host-cache tier composed with the device scan
(DESIGN.md §14).

`build_tier_step` assembles a scan step with the same contract as the
policy engine's `build_step`, over the composed carry — `SimState` with
`hostcache=HCState`. Per trace op the host tier decides hit / miss /
allocate / evict / flush from its set-associative state, then drives the
*unmodified* policy-engine core over a fixed-shape stream of K device
sub-ops:

    slot 0        — the trace op itself, or a pad when the host tier
                    absorbed it (read hit; write hit/allocate in
                    write-back mode)
    slot 1        — the eviction write-back of a dirty LRU victim, or a
                    pad
    slots 2..K-1  — scheduled dirty-flush writes (watermark burst or
                    idle-gap), or pads

Inactive slots are pads (`is_write == -1`), which the engine core
already treats as provable no-ops (zero latency, carry unchanged, the
residency entry written back as-is) — so the device sees *exactly* the
post-host-cache op stream and nothing else. Flush and eviction writes
are real device writes at the op's arrival time: they land in the SLC
cache, consume device counters (CTR host_w), occupy plane service time
and trigger reclamation — which is precisely the two-level interaction
(write-back flush bursts slamming into SLC-cache reclamation) this
stage exists to make simulable.

Host-absorbed ops are served at `HCParams.hit_ms` and, crucially, do
not advance the device's `prev_t`: the device's idle accounting sees
the gaps between *device-visible* ops, as a real device would.

Everything here is shape-static per `HostCacheSpec` (sets/ways fix the
line arrays, flush_per_op fixes K) and branch-static per its
mode/promote/flush axes — the spec is the jit key; the float knobs are
traced (`HCParams`) and never recompile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ssd.policies.engine import _build_core, reduced_of
from repro.core.ssd.policies.registry import resolve_spec
from repro.core.ssd.policies.state import CellParams, SimState
from repro.hostcache.model import H_CTR, HCState
from repro.hostcache.spec import HostCacheSpec
from repro.telemetry import probe

__all__ = ["build_tier_step"]

# plain int (not a jnp scalar): this module is imported lazily, possibly
# inside a jit trace — a module-level jnp constant would be born a tracer
_INT_BIG = 2**31 - 1


def build_tier_step(cfg, policy, hc_spec: HostCacheSpec, *,
                    closed_loop: bool, params: CellParams):
    """Returns the composed scan step for (composition, mode, hostcache
    spec). Same carry/output contract as `engine.build_step`: with the
    telemetry probe off the step emits the op latency; with it on,
    `(latency, probe_row, host_row)` — the extra host row carries the
    cumulative host counters, the dirty-line fraction and the cumulative
    device-visible latency, reduced post-scan by `model.host_windows`."""
    if params.hostcache is None:
        raise ValueError("build_tier_step needs CellParams.hostcache "
                         "(model.as_hc_params of the spec)")
    spec = resolve_spec(policy)
    core = _build_core(cfg, spec, closed_loop=closed_loop, params=params)
    hcp = params.hostcache
    p_total = cfg.num_planes
    cap_basic = params.cap_basic
    cap_trad = params.cap_trad
    cap_boost = (jnp.int32(0) if params.cap_boost is None
                 else params.cap_boost)

    s_n, w_n = hc_spec.sets, hc_spec.ways
    n_flush = hc_spec.flush_per_op
    mode, promote, flush = hc_spec.mode, hc_spec.promote, hc_spec.flush
    lines_f = jnp.float32(s_n * w_n)
    w_idx = jnp.arange(w_n, dtype=jnp.int32)
    f_idx = jnp.arange(n_flush, dtype=jnp.int32)

    def step(state: SimState, op):
        hc: HCState = state.hostcache
        t = jnp.asarray(op["arrival_ms"], jnp.float32)
        lba, kind = op["lba"], op["is_write"]
        is_pad = kind < 0
        live = ~is_pad
        is_write = kind == 1
        is_read = live & ~is_write

        # ---- host tier: lookup ----
        si = lba % s_n                      # pads carry lba 0 — masked out
        set_tags = hc.tag[si]               # (W,)
        set_dirty = hc.dirty[si]
        set_age = hc.age[si]
        match = (set_tags == lba) & live
        hit = jnp.any(match)
        way = jnp.argmax(match)
        tick = hc.tick + live.astype(jnp.int32)   # >= 1 on any live op

        # ---- promotion filter (miss-insert gate) ----
        if promote == "always":
            promote_ok = live
            shadow_tag_new, shadow_cnt_new = hc.shadow_tag, hc.shadow_cnt
        elif promote == "write":            # write misses allocate, read
            promote_ok = is_write           # misses go to the device alone
            shadow_tag_new, shadow_cnt_new = hc.shadow_tag, hc.shadow_cnt
        else:
            sh_match = hc.shadow_tag[si] == lba
            cnt = jnp.where(sh_match, hc.shadow_cnt[si] + 1, jnp.int32(1))
            promote_ok = cnt.astype(jnp.float32) >= hcp.promote_n
            upd = live & ~hit               # filter observes misses only
            shadow_tag_new = hc.shadow_tag.at[si].set(
                jnp.where(upd, lba, hc.shadow_tag[si]))
            shadow_cnt_new = hc.shadow_cnt.at[si].set(
                jnp.where(upd, cnt, hc.shadow_cnt[si]))

        # ---- allocate-on-miss / victim ----
        if mode == "wa":                    # write-around never allocates
            want_insert = is_read & ~hit    # on writes
        else:
            want_insert = live & ~hit
        do_insert = want_insert & promote_ok
        vic = jnp.argmin(set_age)           # LRU; invalid lines (age 0) lose
        vic_tag = set_tags[vic]
        vic_dirty = (set_dirty[vic] > 0) & (vic_tag >= 0)
        evict_wb = do_insert & vic_dirty    # only reachable in wb mode

        # ---- absorption (ops the device never sees) ----
        if mode == "wb":
            absorbed_w = is_write & (hit | do_insert)
        else:
            absorbed_w = is_write & False   # wt/wa writes always hit device
        absorbed_r = is_read & hit
        absorbed = absorbed_r | absorbed_w

        # ---- line-array update (one set row rebuilt, scattered back) ----
        hit_mask = (w_idx == way) & hit
        ins_mask = (w_idx == vic) & do_insert
        tag_row = set_tags
        age_row = jnp.where(hit_mask, tick, set_age)
        dirty_row = set_dirty
        d_delta = jnp.int32(0)
        if mode == "wa":
            # a write hit is superseded by the device write: invalidate
            inval = hit_mask & is_write
            tag_row = jnp.where(inval, -1, tag_row)
            age_row = jnp.where(inval, 0, age_row)
        if mode == "wb":
            newly_dirty = is_write & hit & (set_dirty[way] == 0)
            dirty_row = jnp.where(hit_mask & is_write, 1, dirty_row)
            d_delta = d_delta + newly_dirty.astype(jnp.int32)
        tag_row = jnp.where(ins_mask, lba, tag_row)
        age_row = jnp.where(ins_mask, tick, age_row)
        if mode == "wb":
            ins_dirty = is_write & do_insert
            dirty_row = jnp.where(ins_mask, ins_dirty.astype(jnp.int32),
                                  dirty_row)
            d_delta = (d_delta + ins_dirty.astype(jnp.int32)
                       - evict_wb.astype(jnp.int32))
        else:
            dirty_row = jnp.where(ins_mask, 0, dirty_row)
        tag_new = hc.tag.at[si].set(tag_row)
        dirty_new = hc.dirty.at[si].set(dirty_row)
        age_new = hc.age.at[si].set(age_row)
        dirty_n = hc.dirty_n + d_delta

        # ---- flush scheduling (dirty lines exist only in wb mode) ----
        if mode == "wb" and flush == "watermark":
            # hysteresis latch: arm at wm_hi, drain in bursts of
            # `flush_per_op` per op until wm_lo — the flush-burst shape
            df = dirty_n.astype(jnp.float32)
            flushing = jnp.where(
                df >= hcp.wm_hi * lines_f, jnp.int32(1),
                jnp.where(df <= hcp.wm_lo * lines_f, jnp.int32(0),
                          hc.flushing))
            flush_on = (flushing == 1) & live
        elif mode == "wb" and not closed_loop:   # idle-gap flush (replay)
            flushing = hc.flushing
            gap = jnp.maximum(t - hc.prev_t, 0.0)
            flush_on = live & (gap > hcp.flush_gap_ms) & (dirty_n > 0)
        else:       # wt/wa never dirty; closed-loop idle flush never fires
            flushing = hc.flushing
            flush_on = live & False
        # round-robin set cursor; per slot, the set's oldest dirty way
        flush_sets = jnp.mod(hc.fcur + f_idx, s_n)       # (F,) distinct
        frows_d = dirty_new[flush_sets]                  # (F, W)
        has_dirty = jnp.any(frows_d > 0, axis=1)
        fway = jnp.argmin(jnp.where(frows_d > 0, age_new[flush_sets],
                                    _INT_BIG), axis=1)
        do_flush = flush_on & has_dirty                  # (F,)
        flush_tag = jnp.take_along_axis(
            tag_new[flush_sets], fway[:, None], axis=1)[:, 0]
        dirty_new = dirty_new.at[flush_sets, fway].set(
            jnp.where(do_flush, 0, dirty_new[flush_sets, fway]))
        n_flushed = jnp.sum(do_flush.astype(jnp.int32))
        dirty_n = dirty_n - n_flushed
        fcur_new = jnp.where(flush_on, jnp.mod(hc.fcur + n_flush, s_n),
                             hc.fcur)

        # ---- the device-visible sub-op stream (pads are no-ops) ----
        main_kind = jnp.where(absorbed, jnp.int32(-1), kind)
        main_lba = jnp.where(absorbed, jnp.int32(0), lba)
        ev_kind = jnp.where(evict_wb, jnp.int32(1), jnp.int32(-1))
        ev_lba = jnp.where(evict_wb, vic_tag, jnp.int32(0))
        fl_kind = jnp.where(do_flush, jnp.int32(1), jnp.int32(-1))
        fl_lba = jnp.where(do_flush, flush_tag, jnp.int32(0))
        sub_ops = {
            "arrival_ms": jnp.broadcast_to(t, (2 + n_flush,)),
            "lba": jnp.concatenate(
                [jnp.stack([main_lba, ev_lba]), fl_lba]),
            "is_write": jnp.concatenate(
                [jnp.stack([main_kind, ev_kind]), fl_kind]),
        }

        def sub(carry, sop):
            red, loc, loc_ep, wear = carry
            slba = sop["lba"]
            red2, out = core(red, sop, loc[slba], loc_ep[slba], wear=wear)
            return ((red2, loc.at[slba].set(out.loc_val),
                     loc_ep.at[slba].set(out.loc_ep_val), out.wear),
                    (out.latency, out.occ_delta, out.idle_claim))

        (red, loc, loc_ep, wear), (lat_k, occ_k, idle_k) = jax.lax.scan(
            sub, (reduced_of(state), state.loc, state.loc_ep, state.wear),
            sub_ops)
        latency = jnp.where(absorbed, hcp.hit_ms, lat_k[0])
        # device-visible latency: every live sub-op's service time —
        # unmasked by absorption, so flush-burst-vs-reclamation queueing
        # stays observable even when the host tier absorbs all writes
        dev_lat = hc.dev_lat_ms + jnp.sum(
            jnp.where(sub_ops["is_write"] >= 0, lat_k, 0.0))

        hctr_new = hc.hctr + jnp.stack([        # order == H_CTR
            hit.astype(jnp.float32),
            absorbed_r.astype(jnp.float32),
            (hit & is_write).astype(jnp.float32),
            absorbed.astype(jnp.float32),
            absorbed_w.astype(jnp.float32),
            (live & ~absorbed).astype(jnp.float32),
            n_flushed.astype(jnp.float32),
            evict_wb.astype(jnp.float32)])
        hc_new = HCState(
            tag=tag_new, dirty=dirty_new, age=age_new,
            shadow_tag=shadow_tag_new, shadow_cnt=shadow_cnt_new,
            tick=tick, dirty_n=dirty_n, flushing=flushing, fcur=fcur_new,
            prev_t=jnp.where(live, t, hc.prev_t), hctr=hctr_new,
            dev_lat_ms=dev_lat, hwin=hc.hwin)
        new_state = SimState(
            wear=wear, busy=red.busy, slc_used=red.slc_used,
            rp_done=red.rp_done, trad_used=red.trad_used,
            valid_mig=red.valid_mig, epoch=red.epoch,
            loc=loc, loc_ep=loc_ep, counters=red.counters,
            prev_t=red.prev_t, idle_cum=red.idle_cum,
            idle_seen=red.idle_seen, hostcache=hc_new)

        if state.timeline is not None:
            cap_tot = ((cap_basic + cap_boost + cap_trad)
                       .astype(jnp.float32) * p_total)
            # the probe's wear column stays off under the tier pipeline
            # (sub-op max_cycles don't reduce to one per-op scalar);
            # run_trace/run_fleet window with endurance=False to match
            tl_new, tl_row = probe.accumulate(
                state.timeline, is_pad=is_pad, counters=red.counters,
                occ_delta=jnp.sum(occ_k), cap_pages=cap_tot,
                idle_claim=idle_k[0], wear=None)
            hrow = jnp.concatenate(
                [hctr_new, (dirty_n.astype(jnp.float32) / lines_f)[None],
                 dev_lat[None]])
            return (new_state._replace(timeline=tl_new),
                    (latency, tl_row, hrow))
        return new_state, latency

    return step
