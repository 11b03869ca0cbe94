"""Plain reference of a host block-cache tier in front of the paper's
drive, independent of the program.

Per trace op the tier looks the page up in its set (`lba % sets`, LRU by
tick ages), decides whether a miss allocates (the `promote` gate:
`always`, `nth` access of a shadow candidate, or `write` misses only),
picks its set's LRU victim and writes it back when it is dirty, marks
written lines dirty (write-back), and, while the dirty fraction's
hysteresis latch is armed (`flush` `watermark`: arm at `wm_hi`, disarm at
`wm_lo`) or an arrival gap opens (`idle`), writes back the oldest dirty
line of each of `flush_per_op` sets from a round-robin cursor. An op the
tier serves alone (a read hit, a write it holds) costs `hit_ms`; the
rest, with the eviction and flush writes, go to the drive as K = 2 +
`flush_per_op` device sub-ops a step (the op or a pad, the eviction or a
pad, the flushes or pads), each through `benchlib.reference`'s golden
step. The tier counts what it did with its own integer counters and
counts dirty lines by summing its dirty bits.

The tier comes from the configuration's `point.hostcache` group over the
defaults below; traces from the traffic recipe (`benchlib.synth`). The
AGC waste of a `phases` trace is fitted from its daily request-level
build, as `fit_stats` estimates write ratio and sequentiality; an `msr`
trace's comes from its published stats. Summaries are reduced in float64
on the host, on the host's CPU device. The float type `ftype` is the
configuration's float32, or bfloat16 for the control.
"""
from __future__ import annotations

import dataclasses
import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from benchlib import reference, synth

THREADS = 4
OWN_FIELDS = ("trace", "mode", "policy", "seed", "baseline", "hostcache")
DEFAULTS = {"mode": "wb", "promote": "always", "flush": "watermark",
            "sets": 128, "ways": 8, "flush_per_op": 2, "promote_n": 2.0,
            "wm_hi": 0.75, "wm_lo": 0.5, "hit_ms": 0.002,
            "flush_gap_ms": 5.0}
CHOICES = {"mode": ("wb", "wt", "wa"), "promote": ("always", "nth", "write"),
           "flush": ("watermark", "idle")}
COUNTS = ("hits", "read_hits", "write_hits", "absorbed", "absorbed_w",
          "dev_ops", "flush_w", "evict_w")
C = {name: i for i, name in enumerate(COUNTS)}


class Tier(NamedTuple):
    """The tier as the configuration states it (a jit static argument)."""
    mode: str
    promote: str
    flush: str
    sets: int
    ways: int
    flush_per_op: int
    promote_n: float
    wm_hi: float
    wm_lo: float
    hit_ms: float
    flush_gap_ms: float


def tier_of(config: dict) -> Tier:
    group = config.get("point", {}).get("hostcache")
    if group is None:
        raise ValueError("the host-tier reference needs a configuration "
                         "with a point.hostcache group")
    unknown = set(group) - set(DEFAULTS)
    if unknown:
        raise ValueError(f"host tier: unknown fields {sorted(unknown)}")
    tier = Tier(**(DEFAULTS | group))
    for field, values in CHOICES.items():
        if getattr(tier, field) not in values:
            raise ValueError(f"host tier: {field} {getattr(tier, field)!r} "
                             f"not in {values}")
    return tier


class TierState(NamedTuple):
    tag: object         # (S, W) resident lba, -1 empty
    dirty: object       # (S, W) bool
    age: object         # (S, W) tick of the last touch, 0 empty
    cand: object        # (S,) nth gate: the set's candidate lba
    cand_n: object      # (S,) its accesses seen
    tick: object
    armed: object       # the watermark latch
    cursor: object      # the next set to flush
    last_t: object      # the last live arrival
    counts: object      # (len(COUNTS),) int32
    dev_lat: object     # device latency of every live sub-op, summed


def _init_tier(tier: Tier, ftype):
    import jax.numpy as jnp
    s, w = tier.sets, tier.ways
    return TierState(
        tag=jnp.full((s, w), -1, jnp.int32),
        dirty=jnp.zeros((s, w), bool), age=jnp.zeros((s, w), jnp.int32),
        cand=jnp.full(s, -1, jnp.int32), cand_n=jnp.zeros(s, jnp.int32),
        tick=jnp.int32(0), armed=jnp.bool_(False), cursor=jnp.int32(0),
        last_t=jnp.asarray(0.0, ftype),
        counts=jnp.zeros(len(COUNTS), jnp.int32),
        dev_lat=jnp.asarray(0.0, ftype))


def _tier_step(tier: Tier, closed_loop: bool, ftype):
    """One trace op through the tier: its new state, whether the tier
    served it alone, and the K device sub-ops (kinds, lbas)."""
    import jax.numpy as jnp
    f = ftype
    lines = jnp.asarray(tier.sets * tier.ways, f)
    ways = jnp.arange(tier.ways)
    big = jnp.iinfo(jnp.int32).max

    def step(st: TierState, t, lba, kind):
        live = kind >= 0
        is_write = kind == 1
        is_read = live & ~is_write
        s = lba % tier.sets
        row_tag, row_dirty, row_age = st.tag[s], st.dirty[s], st.age[s]
        match = live & (row_tag == lba)
        hit = jnp.any(match)
        way = jnp.argmax(match)
        tick = st.tick + live.astype(jnp.int32)

        cand, cand_n = st.cand, st.cand_n
        if tier.promote == "always":
            gate = live
        elif tier.promote == "write":
            gate = is_write
        else:                               # nth
            n = jnp.where(st.cand[s] == lba, st.cand_n[s] + 1, 1)
            gate = n.astype(jnp.float32) >= jnp.float32(tier.promote_n)
            seen = live & ~hit
            cand = cand.at[s].set(jnp.where(seen, lba, st.cand[s]))
            cand_n = cand_n.at[s].set(jnp.where(seen, n, st.cand_n[s]))
        # write-around allocates on read misses alone
        insert = (is_read if tier.mode == "wa" else live) & ~hit & gate
        victim = jnp.argmin(row_age)
        evict = insert & row_dirty[victim] & (row_tag[victim] >= 0)
        held_w = (is_write & (hit | insert) if tier.mode == "wb"
                  else jnp.bool_(False))
        absorbed = (is_read & hit) | held_w

        touched = (ways == way) & hit
        placed = (ways == victim) & insert
        age = jnp.where(touched, tick, row_age)
        tag, dirty = row_tag, row_dirty
        if tier.mode == "wa":
            gone = touched & is_write       # the device write supersedes it
            tag = jnp.where(gone, -1, tag)
            age = jnp.where(gone, 0, age)
        if tier.mode == "wb":
            dirty = dirty | (touched & is_write)
        tag = jnp.where(placed, lba, tag)
        age = jnp.where(placed, tick, age)
        dirty = jnp.where(placed, is_write & (tier.mode == "wb"), dirty)
        all_tag = st.tag.at[s].set(tag)
        all_dirty = st.dirty.at[s].set(dirty)
        all_age = st.age.at[s].set(age)
        n_dirty = jnp.sum(all_dirty).astype(f)

        armed = st.armed
        if tier.mode != "wb":
            flushing = jnp.bool_(False)
        elif tier.flush == "watermark":
            armed = jnp.where(n_dirty >= jnp.asarray(tier.wm_hi, f) * lines,
                              True,
                              jnp.where(n_dirty <= jnp.asarray(tier.wm_lo, f)
                                        * lines, False, armed))
            flushing = armed & live
        elif tier.flush == "idle" and not closed_loop:
            gap = jnp.maximum(t - st.last_t, jnp.asarray(0.0, f))
            flushing = live & (gap > jnp.asarray(tier.flush_gap_ms, f)) & (
                n_dirty > 0)
        else:
            flushing = jnp.bool_(False)
        flush_kind, flush_lba = [], []
        n_flushed = jnp.int32(0)
        for j in range(tier.flush_per_op):
            fs = (st.cursor + j) % tier.sets
            d = all_dirty[fs]
            fw = jnp.argmin(jnp.where(d, all_age[fs], big))
            go = flushing & jnp.any(d)
            flush_kind.append(jnp.where(go, 1, -1))
            flush_lba.append(jnp.where(go, all_tag[fs, fw], 0))
            all_dirty = all_dirty.at[fs, fw].set(all_dirty[fs, fw] & ~go)
            n_flushed += go.astype(jnp.int32)
        cursor = jnp.where(flushing,
                           (st.cursor + tier.flush_per_op) % tier.sets,
                           st.cursor)

        kinds = jnp.stack([jnp.where(absorbed, -1, kind),
                           jnp.where(evict, 1, -1)] + flush_kind)
        lbas = jnp.stack([jnp.where(absorbed, 0, lba),
                          jnp.where(evict, row_tag[victim], 0)] + flush_lba)
        add = jnp.stack([x.astype(jnp.int32) for x in (
            hit, is_read & hit, is_write & hit, absorbed, held_w,
            live & ~absorbed)] + [n_flushed, evict.astype(jnp.int32)])
        new = TierState(tag=all_tag, dirty=all_dirty, age=all_age,
                        cand=cand, cand_n=cand_n, tick=tick, armed=armed,
                        cursor=cursor,
                        last_t=jnp.where(live, t, st.last_t),
                        counts=st.counts + add, dev_lat=st.dev_lat)
        return new, absorbed, kinds, lbas

    return step


@functools.lru_cache(maxsize=None)
def _scan_fn(drive: reference.Drive, tier: Tier, policy: str,
             closed_loop: bool, ftype_name: str):
    """Jitted (C, T) scan of a group of cells of one policy and mode."""
    import jax
    import jax.numpy as jnp
    f = jnp.dtype(ftype_name)
    device = reference._device_step(drive, policy, closed_loop, f)
    host = _tier_step(tier, closed_loop, f)
    hit_ms = jnp.asarray(tier.hit_ms, f)

    def one(arrival, lba, kind, cap_basic, cap_trad, idle_thr, waste_p):
        prm = reference.Params(cap_basic, cap_trad, idle_thr.astype(f),
                               waste_p.astype(f))

        def body(carry, op):
            dev, st = carry
            t = op[0].astype(f)
            st, absorbed, kinds, lbas = host(st, t, op[1], op[2])
            lats = []
            for k in range(kinds.shape[0]):
                dev, lat = device(dev, t, lbas[k], kinds[k], prm)
                lats.append(lat)
            lats = jnp.stack(lats)
            st = st._replace(dev_lat=st.dev_lat + jnp.sum(
                jnp.where(kinds >= 0, lats, jnp.asarray(0.0, f))))
            return (dev, st), jnp.where(absorbed, hit_ms, lats[0])

        carry0 = (reference._init_dev(drive, f), _init_tier(tier, f))
        return jax.lax.scan(body, carry0, (arrival, lba, kind))

    return jax.jit(jax.vmap(one))


def fitted_waste(recipe: dict, drive: reference.Drive, seed: int) -> float:
    """AGC waste of a `phases` trace: write ratio and sequentiality of its
    daily requests, estimated as the program's stats fit does (a request
    is sequential when it starts at the previous one's end, modulo the
    synthesizer's wrap window)."""
    req = synth.phases(recipe, drive.n_logical, seed, drive.total_pages)
    lba = np.asarray(req["lba"], np.int64)
    pages = np.asarray(req["pages"], np.int64)
    wrap = max(drive.n_logical - 16, 1)
    seq = float((lba[1:] == (lba[:-1] + pages[:-1]) % wrap).mean()) \
        if len(lba) > 1 else 0.0
    return reference.agc_waste({
        "write_ratio": float(np.asarray(req["is_write"], bool).mean()),
        "seq_prob": seq})


def waste_of(recipe: dict, drive: reference.Drive, seed: int) -> float:
    if recipe["kind"] == "msr":
        return reference.agc_waste(recipe["stats"])
    if recipe["kind"] == "phases":
        return fitted_waste(recipe, drive, seed)
    raise ValueError(f"unknown recipe kind {recipe['kind']!r}")


def plain(point) -> None:
    """Refuse a point with a field this reference does not simulate."""
    if point.hostcache is None:
        raise ValueError(f"point {point.key}: no host tier")
    for f in dataclasses.fields(point):
        v = getattr(point, f.name)
        if f.name not in OWN_FIELDS and v != f.default:
            raise ValueError(f"point {point.key}: the host-tier reference "
                             f"does not simulate {f.name}={v!r}")


def simulate(drive: reference.Drive, tier: Tier, policy: str, mode: str,
             traces: list, wastes: list, ftype_name: str = "float32",
             device=None) -> list:
    """Summaries of a group of cells (one policy, one mode), with the
    host tier's keys."""
    import jax
    import jax.numpy as jnp
    cpu = device or jax.devices("cpu")[0]
    prm = [reference.params_of(drive, policy, w) for w in wastes]
    if len({len(t["arrival_ms"]) for t in traces}) != 1:
        raise ValueError("a reference group needs one padded length")
    with jax.default_device(cpu):
        args = (jnp.asarray(np.stack([t["arrival_ms"] for t in traces])),
                jnp.asarray(np.stack([t["lba"] for t in traces])),
                jnp.asarray(np.stack([t["is_write"] for t in traces]),
                            jnp.int32),
                jnp.asarray([p[0] for p in prm], jnp.int32),
                jnp.asarray([p[1] for p in prm], jnp.int32),
                jnp.asarray([p[2] for p in prm], jnp.float32),
                jnp.asarray([p[3] for p in prm], jnp.float32))
        fn = _scan_fn(drive, tier, policy, mode == "bursty", ftype_name)
        (dev, st), latency = fn(*args)
        latency = np.asarray(latency.astype(jnp.float32))
        dev = jax.tree.map(lambda x: np.asarray(
            x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x), dev)
        counts = np.asarray(st.counts, np.float64)
        dev_lat = np.asarray(st.dev_lat.astype(jnp.float32), np.float64)
    ctr = reference._flush(drive, policy, dev) if mode == "daily" else \
        np.asarray(dev.counters, np.float64)
    is_write = np.stack([t["is_write"] for t in traces])
    out = reference._summaries(latency, is_write, ctr,
                               [t["n_ops"] for t in traces])
    for i, s in enumerate(out):
        h = counts[i]
        s.update({
            "host_hit_rate": h[C["hits"]] / max(h[C["absorbed"]]
                                                + h[C["dev_ops"]], 1.0),
            "host_absorbed": h[C["absorbed"]],
            "host_absorbed_w": h[C["absorbed_w"]],
            "host_dev_ops": h[C["dev_ops"]],
            "host_flush_w": h[C["flush_w"]],
            "host_evict_w": h[C["evict_w"]],
            "host_dev_write_frac": ctr[i, reference.CTR["host_w"]]
            / max(float((is_write[i] == 1).sum()), 1.0),
            "host_dev_lat_ms": float(dev_lat[i])})
    return out


def results(config: dict, traffic: dict, points: list,
            ftype: str = "float32", device=None) -> List[Dict]:
    drive = reference.drive_of(config)
    tier = tier_of(config)
    groups: Dict[tuple, list] = {}
    for i, p in enumerate(points):
        plain(p)
        groups.setdefault((p.policy, p.mode), []).append(i)

    def one(key):
        policy, mode = key
        idx = groups[key]
        traces, wastes = [], []
        for i in idx:
            p = points[i]
            recipe = traffic["traces"][p.trace]
            traces.append(synth.truncated(synth.build(
                p.trace, recipe, drive.n_logical, drive.total_pages, mode,
                p.seed), traffic.get("max_ops")))
            wastes.append(waste_of(recipe, drive, p.seed))
        return idx, simulate(drive, tier, policy, mode, traces, wastes,
                             ftype, device)

    out: List[Optional[Dict]] = [None] * len(points)
    with ThreadPoolExecutor(max(1, min(THREADS, len(groups)))) as ex:
        for idx, summ in ex.map(one, sorted(groups)):
            for i, s in zip(idx, summ):
                out[i] = s
    return out
