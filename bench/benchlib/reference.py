"""Plain reference of the simulated drive, independent of the program.

What it computes, per cell: the per-op scan of one padded trace through
the paper's four SLC-cache policies (a copy of the pre-engine monolithic
step that the repository keeps as its golden), then the end-of-workload
flush and the summary every sweep cell reports. It imports nothing of the program and takes nothing the program
made: the drive comes from the configuration file, the traces from
`benchlib.synth` and the traffic file's recipe.

The step is written once over a float type `ftype`: float32 is the
configuration's stated precision; bfloat16 is the control (the nearest
precision below), which the comparison has to fail.

Summaries are reduced in float64 on the host. Everything runs on the
host's CPU device, so the reference never touches the chip's memory.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np

PAPER_POLICIES = ("baseline", "ips", "ips_agc", "coop")
WATERMARK_NUM, WATERMARK_DEN = 7, 8
OVERRUN_PAGES = 4
CTR = {name: i for i, name in enumerate(
    ["host_w", "slc_w", "tlc_w", "rp_host", "rp_agc", "rp_trad",
     "mig_w", "erases", "agc_waste", "conflict_ms"])}


@dataclasses.dataclass(frozen=True)
class Drive:
    """The drive as the configuration file states it (hashable: a jit
    static argument)."""
    num_planes: int
    total_pages: int
    n_logical: int
    pages_per_slc_block: int
    slc_cap_pages: int
    coop_ips_pages: int
    coop_trad_pages: int
    idle_threshold_ms: float
    slc_read_ms: float
    tlc_read_ms: float
    slc_write_ms: float
    tlc_write_ms: float
    erase_ms: float
    reprogram_ms: float


def drive_of(config: dict) -> Drive:
    """The reference's drive from a configuration file's `drive` group."""
    d = config["drive"]
    t = config["timing_ms"]
    planes = (d["channels"] * d["chips_per_channel"] * d["dies_per_chip"]
              * d["planes_per_die"])
    total = planes * d["blocks_per_plane"] * d["pages_per_block"]
    page_bytes = d["page_kb"] * 1024

    def per_plane(gb):
        return max(int(gb * 1024 ** 3 / page_bytes / planes), 4)

    return Drive(
        num_planes=planes, total_pages=total,
        n_logical=min(total, d["logical_pages_cap"]),
        pages_per_slc_block=d["pages_per_block"] // d["slc_density_ratio"],
        slc_cap_pages=per_plane(d["slc_cache_gb"]),
        coop_ips_pages=per_plane(d["coop_ips_gb"]),
        coop_trad_pages=per_plane(d["coop_traditional_gb"]),
        idle_threshold_ms=float(d["idle_threshold_ms"]),
        **{k: float(t[k]) for k in ("slc_read_ms", "tlc_read_ms",
                                    "slc_write_ms", "tlc_write_ms",
                                    "erase_ms", "reprogram_ms")})


def agc_waste(stats: dict) -> float:
    """AGC early-migration waste per reprogrammed page, from the trace's
    write ratio and sequentiality (the paper's calibration)."""
    pressure = stats["write_ratio"] * (1.0 - stats["seq_prob"])
    return float(min(0.15 * pressure + 0.02, 0.2))


class Params(NamedTuple):
    cap_basic: object
    cap_trad: object
    idle_thr: object
    waste_p: object


def params_of(drive: Drive, policy: str, waste_p: float) -> tuple:
    coop = policy == "coop"
    return (drive.coop_ips_pages if coop else drive.slc_cap_pages,
            drive.coop_trad_pages if coop else 0,
            drive.idle_threshold_ms,
            waste_p if policy in ("ips_agc", "coop") else 0.0)


class DevState(NamedTuple):
    busy: object
    slc_used: object
    rp_done: object
    trad_used: object
    valid_mig: object
    epoch: object
    loc: object
    loc_ep: object
    counters: object
    prev_t: object
    idle_cum: object
    idle_seen: object


def _init_dev(drive: Drive, ftype):
    import jax.numpy as jnp
    p = drive.num_planes
    return DevState(
        busy=jnp.zeros(p, ftype), slc_used=jnp.zeros(p, jnp.int32),
        rp_done=jnp.zeros(p, jnp.int32), trad_used=jnp.zeros(p, jnp.int32),
        valid_mig=jnp.zeros(p, jnp.int32), epoch=jnp.zeros(p, jnp.int32),
        loc=jnp.full(drive.n_logical, -1, jnp.int8),
        loc_ep=jnp.zeros(drive.n_logical, jnp.int16),
        counters=jnp.zeros(len(CTR), ftype), prev_t=jnp.asarray(0.0, ftype),
        idle_cum=jnp.asarray(0.0, ftype), idle_seen=jnp.zeros(p, ftype))


def _ceil_div(a, b):
    return (a + b - 1) // b


def _device_step(drive: Drive, policy: str, closed_loop: bool, ftype):
    """One page op through the drive: the golden monolithic step."""
    import jax.numpy as jnp
    if policy not in PAPER_POLICIES:
        raise ValueError(f"the reference models {PAPER_POLICIES}, "
                         f"not {policy!r}")
    p_total = drive.num_planes
    is_baseline = policy == "baseline"
    has_trad = policy == "coop"
    use_runtime_rp = policy in ("ips", "ips_agc", "coop")
    use_idle_agc = policy in ("ips_agc", "coop")
    ppb_slc = drive.pages_per_slc_block
    c_mig = drive.slc_read_ms + drive.tlc_write_ms
    c_agc = drive.tlc_read_ms + drive.reprogram_ms
    c_trad_rp = drive.slc_read_ms + drive.reprogram_ms
    f = ftype

    def step(state: DevState, t, lba, kind, prm: Params):
        cap_basic, cap_trad = prm.cap_basic, prm.cap_trad
        plane = lba % p_total
        is_pad = kind < 0
        is_write = kind == 1
        busy_p = state.busy[plane]
        ctr = state.counters
        slc_used = state.slc_used[plane]
        rp_done = state.rp_done[plane]
        trad_used = state.trad_used[plane]
        valid_mig = state.valid_mig[plane]
        epoch_p = state.epoch[plane]
        conflict = jnp.asarray(0.0, f)
        zero = jnp.asarray(0.0, f)
        idle_cum = state.idle_cum
        if not closed_loop:
            gap = jnp.maximum(t - state.prev_t, zero)
            idle_cum = idle_cum + jnp.where((gap > prm.idle_thr) & ~is_pad,
                                            gap, zero)
            dev_budget = jnp.where(is_pad, zero,
                                   idle_cum - state.idle_seen[plane])
            full_gap = jnp.where(is_pad, zero, jnp.maximum(t - busy_p, zero))
            if is_baseline:
                above_wm = slc_used >= (WATERMARK_NUM * cap_basic
                                        // WATERMARK_DEN)
                overrun_allow = jnp.where(slc_used < cap_basic,
                                          jnp.asarray(OVERRUN_PAGES * c_mig,
                                                      f), zero)
                budget = jnp.where(above_wm, full_gap + overrun_allow,
                                   dev_budget)
                mig = jnp.minimum(valid_mig,
                                  (budget / c_mig).astype(jnp.int32))
                valid_mig -= mig
                used_ms = mig.astype(f) * c_mig
                budget -= used_ms
                ctr = ctr.at[CTR["mig_w"]].add(mig.astype(f))
                blocks = _ceil_div(slc_used, ppb_slc)
                erase_total = blocks.astype(f) * drive.erase_ms
                can_erase = ((valid_mig == 0) & (slc_used > 0)
                             & (budget >= erase_total))
                ctr = ctr.at[CTR["erases"]].add(
                    jnp.where(can_erase, blocks, 0).astype(f))
                epoch_p = epoch_p + can_erase.astype(jnp.int32)
                slc_used = jnp.where(can_erase, 0, slc_used)
                used_ms += jnp.where(can_erase, erase_total, zero)
                conflict += jnp.where(above_wm & is_write,
                                      jnp.maximum(used_ms - full_gap, zero),
                                      zero)
            if has_trad:
                budget = dev_budget
                rp_avail = 2 * slc_used - rp_done
                ops1 = jnp.minimum(jnp.minimum(valid_mig, rp_avail),
                                   (budget / c_trad_rp).astype(jnp.int32))
                rp_done += ops1
                valid_mig -= ops1
                budget -= ops1.astype(f) * c_trad_rp
                ctr = ctr.at[CTR["rp_trad"]].add(ops1.astype(f))
                rp_avail = 2 * slc_used - rp_done
                ops2 = jnp.minimum(jnp.where(rp_avail == 0, valid_mig, 0),
                                   (budget / c_mig).astype(jnp.int32))
                valid_mig -= ops2
                budget -= ops2.astype(f) * c_mig
                ctr = ctr.at[CTR["mig_w"]].add(ops2.astype(f))
                blocks = _ceil_div(trad_used, ppb_slc)
                can_erase = ((valid_mig == 0) & (trad_used > 0)
                             & (budget >= blocks.astype(f) * drive.erase_ms))
                budget -= jnp.where(can_erase,
                                    blocks.astype(f) * drive.erase_ms, zero)
                ctr = ctr.at[CTR["erases"]].add(
                    jnp.where(can_erase, blocks, 0).astype(f))
                epoch_p = epoch_p + can_erase.astype(jnp.int32)
                trad_used = jnp.where(can_erase, 0, trad_used)
            if use_idle_agc:
                rp_avail = 2 * slc_used - rp_done
                if has_trad:
                    rp_avail = jnp.where(valid_mig == 0, rp_avail, 0)
                ops = jnp.minimum(rp_avail,
                                  (full_gap / c_agc).astype(jnp.int32))
                rp_done += ops
                opsf = ops.astype(f)
                ctr = ctr.at[CTR["rp_agc"]].add(opsf)
                ctr = ctr.at[CTR["agc_waste"]].add(opsf * prm.waste_p)
                agc_active = (2 * slc_used - rp_done) > 0
                conflict += jnp.where(agc_active & is_write,
                                      jnp.asarray(c_agc * 0.5, f), zero)
        if use_runtime_rp:
            fresh = (slc_used > 0) & (rp_done >= 2 * slc_used)
            slc_used = jnp.where(fresh, 0, slc_used)
            rp_done = jnp.where(fresh, 0, rp_done)
        if closed_loop:
            wait = zero
            start = busy_p + conflict
        else:
            wait = jnp.maximum(busy_p - t, zero)
            start = t + wait + conflict

        old = state.loc[lba].astype(jnp.int32)
        old_ep = state.loc_ep[lba]
        old_clip = jnp.clip(old, 0, p_total - 1)
        epoch_eff = jnp.where(old_clip == plane, epoch_p,
                              state.epoch[old_clip])
        old_ok = (old >= 0) & (old_ep == epoch_eff.astype(jnp.int16))

        to_slc = is_write & (slc_used < cap_basic)
        to_trad = is_write & has_trad & ~to_slc & (trad_used < cap_trad)
        rp_avail = 2 * slc_used - rp_done
        to_rp = (is_write & use_runtime_rp & ~to_slc & ~to_trad
                 & (rp_avail > 0))
        to_tlc = is_write & ~to_slc & ~to_trad & ~to_rp

        prog_t = jnp.where(to_slc | to_trad, jnp.asarray(drive.slc_write_ms,
                                                         f),
                           jnp.where(to_rp, jnp.asarray(drive.reprogram_ms,
                                                        f),
                                     jnp.asarray(drive.tlc_write_ms, f)))
        read_t = jnp.where(old_ok, jnp.asarray(drive.slc_read_ms, f),
                           jnp.asarray(drive.tlc_read_ms, f))
        service = jnp.where(is_write, prog_t, read_t)
        service = jnp.where(is_pad, zero, service)
        latency = jnp.where(is_pad, zero, wait + conflict + service)
        busy_new = jnp.where(is_pad, busy_p, start + service)

        slc_used += to_slc.astype(jnp.int32)
        trad_used += to_trad.astype(jnp.int32)
        rp_done += to_rp.astype(jnp.int32)
        track_new = to_slc if is_baseline else (
            to_trad if has_trad else jnp.zeros_like(to_slc))
        valid_dec = (is_write & old_ok).astype(jnp.int32)

        ctr = ctr.at[CTR["host_w"]].add(is_write.astype(f))
        ctr = ctr.at[CTR["slc_w"]].add((to_slc | to_trad).astype(f))
        ctr = ctr.at[CTR["tlc_w"]].add(to_tlc.astype(f))
        ctr = ctr.at[CTR["rp_host"]].add(to_rp.astype(f))
        ctr = ctr.at[CTR["conflict_ms"]].add(jnp.where(is_write, conflict,
                                                       zero))
        loc_val = jnp.where(is_write, jnp.where(track_new, plane, -1),
                            old).astype(jnp.int8)
        loc_ep_val = jnp.where(is_write & track_new,
                               epoch_p.astype(jnp.int16), old_ep)
        new = DevState(
            busy=state.busy.at[plane].set(busy_new),
            slc_used=state.slc_used.at[plane].set(slc_used),
            rp_done=state.rp_done.at[plane].set(rp_done),
            trad_used=state.trad_used.at[plane].set(trad_used),
            valid_mig=state.valid_mig.at[plane].set(valid_mig)
            .at[old_clip].add(-valid_dec)
            .at[plane].add(jnp.where(track_new, 1, 0).astype(jnp.int32)),
            epoch=state.epoch.at[plane].set(epoch_p),
            loc=state.loc.at[lba].set(loc_val),
            loc_ep=state.loc_ep.at[lba].set(loc_ep_val),
            counters=ctr,
            prev_t=jnp.where(is_pad, state.prev_t, t),
            idle_cum=idle_cum,
            idle_seen=state.idle_seen.at[plane].set(
                jnp.where(is_pad, state.idle_seen[plane], idle_cum)))
        return new, latency

    return step


@functools.lru_cache(maxsize=None)
def _scan_fn(drive: Drive, policy: str, closed_loop: bool, ftype_name: str):
    """Jitted (C, T) scan over a group of cells of one policy and mode."""
    import jax
    import jax.numpy as jnp
    ftype = jnp.dtype(ftype_name)
    step = _device_step(drive, policy, closed_loop, ftype)

    def one(arrival, lba, kind, cap_basic, cap_trad, idle_thr, waste_p):
        prm = Params(cap_basic, cap_trad, idle_thr.astype(ftype),
                     waste_p.astype(ftype))
        carry0 = _init_dev(drive, ftype)

        def body(carry, op):
            t, lb, k = op
            return step(carry, t.astype(ftype), lb, k, prm)

        final, latency = jax.lax.scan(body, carry0, (arrival, lba, kind))
        return final, latency

    return jax.jit(jax.vmap(one))


def _flush(drive: Drive, policy: str, dev) -> np.ndarray:
    """End-of-workload flush: the tracked region's valid pages migrate to
    TLC and its used blocks are erased. Returns the counters (C, n)."""
    ctr = np.array(dev.counters, np.float64)
    if policy in ("ips", "ips_agc"):
        return ctr
    used = np.asarray(dev.trad_used if policy == "coop" else dev.slc_used,
                      np.int64)
    ctr[:, CTR["mig_w"]] += np.asarray(dev.valid_mig, np.int64).sum(axis=1)
    ppb = drive.pages_per_slc_block
    ctr[:, CTR["erases"]] += ((used + ppb - 1) // ppb).sum(axis=1)
    return ctr


def _summaries(latency: np.ndarray, is_write: np.ndarray, ctr: np.ndarray,
               n_ops) -> list:
    out = []
    for i in range(latency.shape[0]):
        w = is_write[i] == 1
        lat = np.asarray(latency[i], np.float64)
        c = ctr[i]
        hw = max(c[CTR["host_w"]], 1.0)
        s = {"mean_write_latency_ms": float(lat[w].sum() / max(w.sum(), 1)),
             "wa_paper": 1.0 + (c[CTR["mig_w"]] + c[CTR["rp_trad"]]
                                + c[CTR["agc_waste"]]) / hw,
             "wa_raw": 1.0 + (c[CTR["mig_w"]] + c[CTR["rp_trad"]]
                              + c[CTR["rp_agc"]]) / hw,
             "slc_writes": c[CTR["slc_w"]], "tlc_writes": c[CTR["tlc_w"]],
             "reprogram_host": c[CTR["rp_host"]],
             "reprogram_agc": c[CTR["rp_agc"]],
             "reprogram_trad": c[CTR["rp_trad"]],
             "migrations": c[CTR["mig_w"]], "erases": c[CTR["erases"]],
             "host_pages": c[CTR["host_w"]],
             "conflict_ms": c[CTR["conflict_ms"]],
             "n_ops": int(n_ops[i])}
        out.append({k: float(v) if k != "n_ops" else v
                    for k, v in s.items()})
    return out


def simulate(drive: Drive, policy: str, mode: str,
             traces: list, waste_ps: list, ftype_name: str = "float32",
             device=None) -> list:
    """Summaries of a group of cells (one policy, one mode): one dict per
    trace, with the keys a sweep cell reports. Runs on the host's CPU
    unless `device` names another."""
    import jax
    import jax.numpy as jnp
    cpu = device or jax.devices("cpu")[0]
    prm = [params_of(drive, policy, w) for w in waste_ps]
    t_len = {len(t["arrival_ms"]) for t in traces}
    if len(t_len) != 1:
        raise ValueError(f"a reference group needs one padded length, "
                         f"got {sorted(t_len)}")
    with jax.default_device(cpu):
        args = (jnp.asarray(np.stack([t["arrival_ms"] for t in traces])),
                jnp.asarray(np.stack([t["lba"] for t in traces])),
                jnp.asarray(np.stack([t["is_write"] for t in traces]),
                            jnp.int32),
                jnp.asarray([p[0] for p in prm], jnp.int32),
                jnp.asarray([p[1] for p in prm], jnp.int32),
                jnp.asarray([p[2] for p in prm], jnp.float32),
                jnp.asarray([p[3] for p in prm], jnp.float32))
        fn = _scan_fn(drive, policy, mode == "bursty", ftype_name)
        dev, latency = fn(*args)
        latency = np.asarray(latency.astype(jnp.float32))
        dev = jax.tree.map(lambda x: np.asarray(
            x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x), dev)
    ctr = _flush(drive, policy, dev) if mode == "daily" else np.asarray(
        dev.counters, np.float64)
    is_write = np.stack([t["is_write"] for t in traces])
    return _summaries(latency, is_write, ctr, [t["n_ops"] for t in traces])

