"""Fused segment-scan step kernel (DESIGN.md §12).

One `pallas_call` executes the whole (S, K) compressed-segment stream in
a single launch: the reduced carry stays live across the sequential
`fori_loop` over segments instead of being materialized between XLA ops,
and the residency maps live in VMEM refs updated in place. Each lane
applies the policy engine's own `_build_core` closure — the kernel
contributes only the execution *structure*, never a second copy of the
policy arithmetic, so kernel-vs-engine bit-identity reduces to the
executor plumbing this file owns (gather, hazard forwarding, scatter),
which is certified against `ref.run_segments_ref` by
tests/test_step_kernel.py.

Dtype plumbing: the wrapper widens every narrow field (packed int16
plane state, int8 `loc`, int16 `loc_ep`) to int32 on the way in and
casts back on the way out. All of the core's residency comparisons go
through explicit `int16`/`int8` casts, and sign-extension preserves
equality of narrow values, so the widened kernel carry is value-exact
for both the packed and unpacked `SimState` layouts.

Residency gathers/scatters are per-lane scalar ref reads and writes at
dynamic `pl.ds` indices. This is not yet a TPU kernel: the v5e compiler
refuses the dynamic indexing of loaded vectors (`lba_k[i]`,
`buf_loc[j]`, `.at[i].set`), which lowers to `dynamic_slice` (DESIGN.md
§12). Superseded lanes (host-side hazard plan,
`workloads.compress`) scatter through a clamped index that writes back
the value just read: drop-mode scatter spelled branchlessly, exact
because the fori loops are sequential. `interpret=True` runs the same
kernel body on any backend and is the CI equivalence gate
(scripts/ci_check.sh); compositions needing wear state are per-op-path
only, same as `build_segment_step`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ssd.policies.engine import Reduced, _build_core
from repro.core.ssd.policies.registry import resolve_spec
from repro.core.ssd.policies.state import CellParams

__all__ = ["run_segments_kernel"]


def _segment_stream_kernel(arr_ref, lba_ref, isw_ref, src_ref, scat_ref,
                           pf_ref, pi_ref,
                           busy_ref, slc_ref, rp_ref, trad_ref, vm_ref,
                           ep_ref, ctr_ref, sc_ref, isn_ref,
                           loc_ref, lep_ref,
                           lat_ref, busy_o, slc_o, rp_o, trad_o, vm_o,
                           ep_o, ctr_o, sc_o, isn_o, loc_o, lep_o,
                           *, cfg, spec, closed_loop, has_boost, n_seg,
                           lanes, n_logical):
    # a Pallas kernel may not capture traced constants, so the per-cell
    # knobs arrive as refs and the core closure is built in-kernel from
    # the reconstructed CellParams (pure jnp — trivially traceable here)
    params = CellParams(
        cap_basic=pi_ref[0], cap_trad=pi_ref[1],
        idle_thr=pf_ref[0], waste_p=pf_ref[1],
        cap_boost=pi_ref[2] if has_boost else None)
    core = _build_core(cfg, spec, closed_loop=closed_loop, params=params)
    # residency maps update in place in the output refs
    loc_o[...] = loc_ref[...]
    lep_o[...] = lep_ref[...]
    red0 = Reduced(busy=busy_ref[...], slc_used=slc_ref[...],
                   rp_done=rp_ref[...], trad_used=trad_ref[...],
                   valid_mig=vm_ref[...], epoch=ep_ref[...],
                   counters=ctr_ref[...], prev_t=sc_ref[0],
                   idle_cum=sc_ref[1], idle_seen=isn_ref[...])

    def seg_body(s, red):
        row = (pl.ds(s, 1), slice(None))
        arr_k = arr_ref[row][0]
        lba_k = lba_ref[row][0]
        isw_k = isw_ref[row][0]
        src_k = src_ref[row][0]
        scat_k = scat_ref[row][0]

        # segment-start residency gather (scalar loads; see module doc)
        def gather(i, bufs):
            old_b, ep_b = bufs
            a = lba_k[i]
            old_b = old_b.at[i].set(loc_o[pl.ds(a, 1)][0])
            ep_b = ep_b.at[i].set(lep_o[pl.ds(a, 1)][0])
            return old_b, ep_b

        old_k, ep_k = jax.lax.fori_loop(
            0, lanes, gather,
            (jnp.zeros(lanes, jnp.int32), jnp.zeros(lanes, jnp.int32)))

        # the lane recurrence: same hazard forwarding as the jnp executor
        def lane(i, acc):
            red_c, buf_loc, buf_ep, lat_row = acc
            use_buf = src_k[i] >= 0
            j = jnp.clip(src_k[i], 0, lanes - 1)
            old = jnp.where(use_buf, buf_loc[j], old_k[i])
            old_ep = jnp.where(use_buf, buf_ep[j], ep_k[i])
            red_n, out = core(
                red_c,
                {"arrival_ms": arr_k[i], "lba": lba_k[i],
                 "is_write": isw_k[i]},
                old, old_ep)
            buf_loc = buf_loc.at[i].set(out.loc_val.astype(jnp.int32))
            buf_ep = buf_ep.at[i].set(out.loc_ep_val.astype(jnp.int32))
            lat_row = lat_row.at[i].set(out.latency)
            return red_n, buf_loc, buf_ep, lat_row

        red, buf_loc, buf_ep, lat_row = jax.lax.fori_loop(
            0, lanes, lane,
            (red, jnp.zeros(lanes, jnp.int32), jnp.zeros(lanes, jnp.int32),
             jnp.zeros(lanes, jnp.float32)))
        lat_ref[row] = lat_row[None, :]

        # duplicate-free scatter: superseded lanes clamp to the last slot
        # and write back the value just read (branchless drop)
        def scatter(i, _):
            a = scat_k[i]
            live = a < n_logical
            idx = jnp.minimum(a, n_logical - 1)
            cur_l = loc_o[pl.ds(idx, 1)][0]
            cur_e = lep_o[pl.ds(idx, 1)][0]
            loc_o[pl.ds(idx, 1)] = jnp.where(live, buf_loc[i], cur_l)[None]
            lep_o[pl.ds(idx, 1)] = jnp.where(live, buf_ep[i], cur_e)[None]
            return 0

        jax.lax.fori_loop(0, lanes, scatter, 0)
        return red

    red = jax.lax.fori_loop(0, n_seg, seg_body, red0)
    busy_o[...] = red.busy
    slc_o[...] = red.slc_used
    rp_o[...] = red.rp_done
    trad_o[...] = red.trad_used
    vm_o[...] = red.valid_mig
    ep_o[...] = red.epoch
    ctr_o[...] = red.counters
    sc_o[...] = jnp.stack([red.prev_t, red.idle_cum])
    isn_o[...] = red.idle_seen


def run_segments_kernel(cfg, policy, segs, state0, *, closed_loop,
                        params, interpret: bool = False):
    """Run the full compressed-segment stream through one kernel launch.

    Same contract as `ref.run_segments_ref`: returns
    `(latency (S, K), (Reduced, loc, loc_ep))` with output dtypes
    matching `state0`'s layout (packed or unpacked)."""
    spec = resolve_spec(policy)
    if params.endurance is not None:
        raise ValueError("fused step kernel does not carry wear state; "
                         "run endurance cells through the per-op step")
    s_cnt, lanes = segs["lba"].shape
    n_logical = state0.loc.shape[0]
    p = state0.busy.shape[0]
    dt_i = state0.slc_used.dtype
    f32, i32 = jnp.float32, jnp.int32

    kern = functools.partial(
        _segment_stream_kernel, cfg=cfg, spec=spec, closed_loop=closed_loop,
        has_boost=params.cap_boost is not None,
        n_seg=s_cnt, lanes=lanes, n_logical=n_logical)
    out_shape = [
        jax.ShapeDtypeStruct((s_cnt, lanes), f32),            # latency
        jax.ShapeDtypeStruct((p,), f32),                      # busy
        *[jax.ShapeDtypeStruct((p,), i32) for _ in range(5)], # plane ints
        jax.ShapeDtypeStruct(state0.counters.shape, f32),     # counters
        jax.ShapeDtypeStruct((2,), f32),                      # prev_t, idle
        jax.ShapeDtypeStruct((p,), f32),                      # idle_seen
        jax.ShapeDtypeStruct((n_logical,), i32),              # loc
        jax.ShapeDtypeStruct((n_logical,), i32),              # loc_ep
    ]
    call = pl.pallas_call(kern, out_shape=out_shape, interpret=interpret)
    (lat, busy, slc, rp, trad, vm, ep, ctr, sc, isn, loc, lep) = call(
        jnp.asarray(segs["arrival_ms"], f32),
        jnp.asarray(segs["lba"], i32),
        jnp.asarray(segs["is_write"], i32),
        jnp.asarray(segs["src"], i32),
        jnp.asarray(segs["scat_lba"], i32),
        jnp.stack([jnp.asarray(params.idle_thr, f32),
                   jnp.asarray(params.waste_p, f32)]),
        jnp.stack([jnp.asarray(params.cap_basic, i32),
                   jnp.asarray(params.cap_trad, i32),
                   jnp.asarray(jnp.int32(0) if params.cap_boost is None
                               else params.cap_boost, i32)]),
        state0.busy,
        state0.slc_used.astype(i32), state0.rp_done.astype(i32),
        state0.trad_used.astype(i32), state0.valid_mig.astype(i32),
        state0.epoch.astype(i32),
        state0.counters,
        jnp.stack([jnp.asarray(state0.prev_t, f32),
                   jnp.asarray(state0.idle_cum, f32)]),
        state0.idle_seen,
        state0.loc.astype(i32), state0.loc_ep.astype(i32))
    red = Reduced(busy=busy, slc_used=slc.astype(dt_i),
                  rp_done=rp.astype(dt_i), trad_used=trad.astype(dt_i),
                  valid_mig=vm.astype(dt_i), epoch=ep.astype(dt_i),
                  counters=ctr, prev_t=sc[0], idle_cum=sc[1],
                  idle_seen=isn)
    return lat, (red, loc.astype(state0.loc.dtype),
                 lep.astype(state0.loc_ep.dtype))
