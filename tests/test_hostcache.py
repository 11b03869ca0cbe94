"""Host-tier block-cache subsystem tests (DESIGN.md §14).

Load-bearing contracts:

* Off-path bit-identity — `hostcache=None` keeps the seed device scan
  exactly: latencies and every SimState field of the four paper policies
  stay bit-identical to the vendored golden monolith (the trailing-carry
  `None` contract; ci_check's off-path gate).
* Conservation — the tier pipeline loses no ops and no writes:
  absorbed + dev_ops equals the live op count exactly, and the device
  write counter equals trace writes minus host-absorbed writes plus
  flush/eviction write-backs, exactly.
* Window telescoping — `HostWindows` per-window deltas sum to the final
  cumulative host counters exactly (the PR 6 snapshot-differencing
  identity), including the device-visible latency column.
* The write-back tier absorbs write traffic (device-visible writes
  strictly below trace writes) and the flush-burst-vs-reclamation cliff
  is visible on the device-visible latency series: the baseline policy
  cliffs early on the bursty flush_burst scenario and IPS shrinks it.
* Fleet/single-cell equivalence extends to the host-cache state.
* Pad-tail trimming is exact for tier fleets: the trimmed scan plus the
  tail replay equals the full-length scan on every leaf and every
  latency, and the sweep runner routes a tier group to it exactly when
  it has a pad tail and neither a timeline nor wear tracking.

Satellite coverage rides along: HostCacheSpec parse/tag validation, the
`hostcache` sweep grid, and report-layer pairing (`hostcache_summary`,
headline geomeans excluding host-tier cells).
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from golden_sim import golden_run_trace
from repro.configs.ssd_paper import PAPER_SSD
from repro.core.ssd import fleet
from repro.core.ssd.driver import _agc_waste_p
from repro.core.ssd.sim import (CTR, SimState, default_params, run_trace,
                                summarize)
from repro.core.ssd.workloads import make_trace, truncate_trace
from repro.hostcache import HostCacheSpec
from repro.hostcache.model import H_CTR, HostWindows, as_hc_params
from repro.sweep.grid import SweepPoint, hostcache_grid, named_grid
from repro.sweep.report import hostcache_summary, policy_geomeans
from repro.telemetry.timeline import detect_cliff
from repro.workloads.compress import TRIM_QUANTUM
from repro.workloads.generators import flush_burst

CFG = PAPER_SSD.scaled(128)
N_LOGICAL = min(CFG.total_pages, 1 << 16)
MAX_OPS = 4096
PAPER_POLICIES = ("baseline", "ips", "ips_agc", "coop")


def _hm0(mode, max_ops=MAX_OPS):
    return truncate_trace(
        make_trace("hm_0", N_LOGICAL, mode=mode,
                   capacity_pages=CFG.total_pages), max_ops)


def _fb(mode, max_ops=None):
    """flush_burst scenario trace, mode-resolved (bursty == the paper's
    sequential-rewrite transform, closed loop)."""
    tr = flush_burst(N_LOGICAL, capacity_pages=CFG.total_pages)
    if mode == "bursty":
        tr = tr.to_bursty(N_LOGICAL)
    if max_ops is not None:
        tr = tr.truncate(max_ops)
    return tr.compile()


def _run_hc(policy, trace, mode, hc, **kw):
    lat, st = run_trace(CFG, policy, trace, closed_loop=mode == "bursty",
                        n_logical=N_LOGICAL, hostcache=hc, **kw)
    return lat, st


def _hctr(st):
    return np.asarray(st.hostcache.hctr, np.float64)


class TestOffPathGoldenIdentity:
    """hostcache=None == the golden monolith, bit for bit."""

    @pytest.mark.parametrize("mode", ["bursty", "daily"])
    @pytest.mark.parametrize("policy", PAPER_POLICIES)
    def test_off_path_vs_golden(self, policy, mode):
        trace = _hm0(mode)
        waste = _agc_waste_p("hm_0")
        closed = mode == "bursty"
        lat_g, st_g = golden_run_trace(CFG, policy, trace,
                                       closed_loop=closed,
                                       n_logical=N_LOGICAL, waste_p=waste)
        lat_o, st_o = run_trace(CFG, policy, trace, closed_loop=closed,
                                n_logical=N_LOGICAL, waste_p=waste,
                                hostcache=None)
        assert st_o.hostcache is None      # statically absent, not zeroed
        assert np.array_equal(np.asarray(lat_g), np.asarray(lat_o)), \
            f"latency mismatch [{policy}/{mode}]"
        for f, val in zip(type(st_g)._fields, st_g):
            assert np.array_equal(np.asarray(val),
                                  np.asarray(getattr(st_o, f))), \
                f"state.{f} mismatch [{policy}/{mode}]"

    def test_default_cell_has_no_hostcache_params(self):
        assert default_params(CFG, "ips").hostcache is None


class TestConservation:
    """The tier pipeline loses no ops and no writes — exact identities."""

    @pytest.mark.parametrize("hc", [
        HostCacheSpec(mode="wb", flush="watermark"),
        HostCacheSpec(mode="wb", flush="idle"),
        HostCacheSpec(mode="wb", promote="write"),
        HostCacheSpec(mode="wt"),
        HostCacheSpec(mode="wa"),
    ], ids=lambda hc: hc.tag)
    def test_op_and_write_conservation(self, hc):
        trace = _fb("daily", max_ops=8192)
        isw = np.asarray(trace["is_write"])
        live = int((isw >= 0).sum())
        trace_w = int((isw == 1).sum())
        _, st = _run_hc("ips", trace, "daily", hc)
        h = _hctr(st)
        # every live op either absorbed at host latency or sent down
        assert h[H_CTR["absorbed"]] + h[H_CTR["dev_ops"]] == live
        assert h[H_CTR["hits"]] == (h[H_CTR["read_hits"]]
                                    + h[H_CTR["write_hits"]])
        # device write counter == trace writes - absorbed + write-backs
        dev_w = float(np.asarray(st.counters)[CTR["host_w"]])
        assert dev_w == (trace_w - h[H_CTR["absorbed_w"]]
                         + h[H_CTR["flush_w"]] + h[H_CTR["evict_w"]])
        if hc.mode in ("wt", "wa"):
            # no dirty lines ever: nothing to flush or write back
            assert h[H_CTR["absorbed_w"]] == 0
            assert h[H_CTR["flush_w"]] == 0 and h[H_CTR["evict_w"]] == 0
            assert dev_w == trace_w

    def test_idle_flush_statically_off_in_closed_loop(self):
        """Bursty replay has no arrival gaps — the idle-gap scheduler
        never fires (and the watermark variant is the only flusher)."""
        trace = _fb("bursty", max_ops=16384)
        _, st = _run_hc("ips", trace, "bursty",
                        HostCacheSpec(mode="wb", flush="idle"))
        assert _hctr(st)[H_CTR["flush_w"]] == 0


class TestWindowTelescoping:
    """HostWindows deltas sum to the final cumulative counters exactly."""

    def test_window_deltas_telescope(self):
        hc = HostCacheSpec()
        trace = _hm0("daily", max_ops=8192)
        _, st = _run_hc("ips", trace, "daily", hc, timeline_ops=512)
        hw = st.hostcache.hwin
        assert isinstance(hw, HostWindows)
        h = _hctr(st)
        for leaf in ("hits", "absorbed", "dev_ops", "flush_w", "evict_w"):
            total = float(np.asarray(getattr(hw, leaf), np.float64).sum())
            assert total == h[H_CTR[leaf]], leaf
        # the device-visible latency column telescopes the same way
        assert (float(np.asarray(hw.dev_lat_ms, np.float64).sum())
                == pytest.approx(float(st.hostcache.dev_lat_ms), rel=1e-6))
        # dirty_frac is a boundary level, not a delta: last snapshot is
        # the final dirty fraction
        assert float(hw.dirty_frac[-1]) == pytest.approx(
            float(st.hostcache.dirty_n) / hc.lines)

    def test_no_probe_no_windows(self):
        trace = _hm0("daily", max_ops=2048)
        _, st = _run_hc("ips", trace, "daily", HostCacheSpec())
        assert st.hostcache.hwin is None


class TestWriteBackAbsorption:
    """The acceptance story: wb absorbs writes, the summary reports it."""

    def test_daily_wb_hits_and_absorbs(self):
        trace = _fb("daily")
        hc = HostCacheSpec(mode="wb", flush="watermark")
        lat, st = _run_hc("ips", trace, "daily", hc)
        isw = np.asarray(trace["is_write"])
        trace_w = int((isw == 1).sum())
        s = summarize(lat, {"is_write": isw}, st,
                      cell=default_params(CFG, "ips")._replace(
                          hostcache=as_hc_params(hc)), cfg=CFG)
        assert float(s["host_hit_rate"]) > 0
        # device-visible writes strictly below trace writes
        dev_w = float(np.asarray(st.counters)[CTR["host_w"]])
        assert dev_w < trace_w
        assert float(s["host_dev_write_frac"]) == pytest.approx(
            dev_w / trace_w)
        # host hits serve at hit_ms: mean write latency collapses vs off
        lat_o, st_o = run_trace(CFG, "ips", trace, closed_loop=False,
                                n_logical=N_LOGICAL)
        s_o = summarize(lat_o, {"is_write": isw}, st_o)
        assert (float(s["mean_write_latency_ms"])
                < float(s_o["mean_write_latency_ms"]))
        assert "host_hit_rate" not in s_o

    def test_bursty_wb_absorbs_without_reuse(self):
        """The sequential-rewrite transform has no address reuse: zero
        hits by construction, yet write-allocation still keeps some dirty
        residue host-side (device writes strictly below trace writes)."""
        trace = _fb("bursty")
        _, st = _run_hc("ips", trace, "bursty", HostCacheSpec())
        h = _hctr(st)
        isw = np.asarray(trace["is_write"])
        trace_w = int((isw == 1).sum())
        assert h[H_CTR["hits"]] == 0
        dev_w = float(np.asarray(st.counters)[CTR["host_w"]])
        assert dev_w < trace_w

    def test_watermark_flushes_more_than_idle_gap(self):
        """On the diurnal scenario the watermark scheduler drains in
        bursts while the idle-gap scheduler rarely opens — evictions
        carry the write-backs instead."""
        trace = _fb("daily")
        flw = {}
        for flush in ("watermark", "idle"):
            _, st = _run_hc("ips", trace, "daily",
                            HostCacheSpec(mode="wb", flush=flush))
            flw[flush] = _hctr(st)
        assert flw["watermark"][H_CTR["flush_w"]] > \
            flw["idle"][H_CTR["flush_w"]]
        assert flw["idle"][H_CTR["evict_w"]] > \
            flw["watermark"][H_CTR["evict_w"]]

    def test_nth_promotion_filters_inserts(self):
        """promote=nth withholds miss-inserts until the shadow filter
        sees N accesses: hit volume can only drop vs promote=always."""
        trace = _hm0("daily", max_ops=8192)
        hits = {}
        for promote in ("always", "nth"):
            _, st = _run_hc("ips", trace, "daily",
                            HostCacheSpec(promote=promote))
            hits[promote] = _hctr(st)[H_CTR["hits"]]
        assert hits["nth"] <= hits["always"]

    @pytest.mark.parametrize("promote,want", [
        # the read miss allocates; the read and the write after it hit
        ("always", dict(hits=3, read_hits=2, write_hits=1, absorbed=3,
                        absorbed_w=1, dev_ops=1)),
        # reads miss twice and allocate nothing; the write miss allocates,
        # and the read after it hits
        ("write", dict(hits=1, read_hits=1, write_hits=0, absorbed=2,
                       absorbed_w=1, dev_ops=2))])
    def test_write_promotion_allocates_on_write_misses_only(self, promote,
                                                             want):
        """promote=write: a read miss goes to the device and leaves its
        set as it was; a write miss allocates (dirty, absorbed); a read
        hit is served from the tier."""
        lba = 4242
        trace = {"arrival_ms": np.arange(4, dtype=np.float32),
                 "lba": np.full(4, lba, np.int32),
                 "is_write": np.array([0, 0, 1, 0], np.int8)}
        _, st = _run_hc("ips", trace, "daily",
                        HostCacheSpec(promote=promote))
        h = _hctr(st)
        assert {k: h[H_CTR[k]] for k in want} == want
        assert h[H_CTR["absorbed"]] + h[H_CTR["dev_ops"]] == 4
        tags = np.asarray(st.hostcache.tag)
        assert (tags == lba).sum() == 1        # resident once, dirty
        assert np.asarray(st.hostcache.dirty)[tags == lba].tolist() == [1]
        # the device saw the trace ops the tier did not absorb, and no
        # write: the written line is still held dirty
        assert float(np.asarray(st.counters)[CTR["host_w"]]) == 0.0

    def test_write_promotion_keeps_reads_out_of_the_tier(self):
        """On a read-only prefix nothing is ever allocated: every read
        misses, goes to the device and leaves every set empty."""
        trace = _hm0("daily", max_ops=8192)
        isw = np.asarray(trace["is_write"]).copy()
        isw[isw == 1] = 0
        trace = dict(trace, is_write=isw)
        _, st = _run_hc("ips", trace, "daily",
                        HostCacheSpec(promote="write"))
        h = _hctr(st)
        assert h[H_CTR["hits"]] == 0 and h[H_CTR["absorbed"]] == 0
        assert h[H_CTR["dev_ops"]] == int((isw >= 0).sum())
        assert (np.asarray(st.hostcache.tag) == -1).all()


class TestFlushBurstCliff:
    """The ISSUE acceptance: the telemetry cliff detector surfaces a
    flush-burst-induced window on the baseline policy that IPS removes
    or shrinks — on the device-visible latency series (the host-visible
    write latency is flat under wb absorption; the cliff lives in what
    the device sees)."""

    def test_baseline_cliffs_ips_shrinks(self):
        hc = HostCacheSpec(mode="wb", flush="watermark")
        trace = _fb("bursty")
        out = {}
        for pol in ("baseline", "ips"):
            _, st = _run_hc(pol, trace, "bursty", hc, timeline_ops=1024)
            hw = st.hostcache.hwin
            dev_n = np.asarray(hw.dev_ops + hw.flush_w + hw.evict_w,
                               np.float64)
            dev_lat = np.asarray(hw.dev_lat_ms, np.float64)
            mean = np.where(dev_n > 0, dev_lat / np.maximum(dev_n, 1),
                            np.nan)
            out[pol] = (detect_cliff(mean, dev_n, window_ops=1024),
                        float(st.hostcache.dev_lat_ms))
        cliff_b, tot_b = out["baseline"]
        cliff_i, tot_i = out["ips"]
        assert cliff_b["detected"]               # baseline hits the cliff
        if cliff_i["detected"]:                  # ... which IPS shrinks:
            assert cliff_i["window"] > cliff_b["window"]   # later onset
        assert tot_i < tot_b                     # less total device time


class TestFleetEquivalence:
    @pytest.mark.parametrize("hc", [
        HostCacheSpec(mode="wb", flush="watermark"),
        HostCacheSpec(mode="wb", promote="write", flush="watermark",
                      wm_hi=0.5, wm_lo=0.45)], ids=lambda hc: hc.tag)
    def test_fleet_matches_single_cell_with_hostcache(self, hc):
        traces = [_hm0("daily", 8192),
                  truncate_trace(
                      make_trace("hm_1", N_LOGICAL, mode="daily",
                                 capacity_pages=CFG.total_pages), 8192)]
        params = [default_params(CFG, "ips")._replace(
            hostcache=as_hc_params(hc))] * 2
        lat_f, st_f = fleet.run_fleet(
            CFG, "ips", fleet.stack_ops(traces),
            fleet.stack_params(params), closed_loop=False,
            n_logical=N_LOGICAL, hostcache=hc)
        for i, tr in enumerate(traces):
            lat_r, st_r = run_trace(CFG, "ips", tr, closed_loop=False,
                                    n_logical=N_LOGICAL, params=params[i],
                                    hostcache=hc)
            assert np.array_equal(np.asarray(lat_r), np.asarray(lat_f[i]))
            for f, val in zip(type(st_r.hostcache)._fields,
                              st_r.hostcache):
                if f == "hwin":
                    continue
                assert np.array_equal(
                    np.asarray(val),
                    np.asarray(getattr(st_f.hostcache, f)[i])), \
                    f"hostcache.{f} mismatch cell {i}"


def _with_tail(trace, n_live, t_len):
    """The first `n_live` ops of `trace`, then `ir.pad_ops`-contract pads
    (the last arrival, lba 0, is_write -1) up to `t_len`."""
    n_pad = t_len - n_live
    return {
        "arrival_ms": np.concatenate([
            np.asarray(trace["arrival_ms"][:n_live], np.float32),
            np.full(n_pad, trace["arrival_ms"][n_live - 1], np.float32)]),
        "lba": np.concatenate([np.asarray(trace["lba"][:n_live], np.int32),
                               np.zeros(n_pad, np.int32)]),
        "is_write": np.concatenate([
            np.asarray(trace["is_write"][:n_live], np.int32),
            np.full(n_pad, -1, np.int32)])}


def _tier_fleet(policy, traces, mode, hc, *, full=False):
    """The tier fleet in the regime `fleet.plan_fleet` gives it, or in
    the full-length reference when `full`."""
    params = [default_params(CFG, policy)._replace(
        hostcache=as_hc_params(hc))] * len(traces)
    ops = fleet.stack_ops(traces)
    t_len = ops["lba"].shape[1]
    return fleet.run_fleet(
        CFG, policy, ops, fleet.stack_params(params),
        closed_loop=mode == "bursty", n_logical=N_LOGICAL, hostcache=hc,
        plan=fleet.FleetPlan(t_len, False) if full else None)


def _assert_same_run(ref, got):
    (lat_r, st_r), (lat_g, st_g) = ref, got
    assert np.array_equal(np.asarray(lat_r), np.asarray(lat_g))
    assert jax.tree.structure(st_r) == jax.tree.structure(st_g)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(st_r)[0],
                            jax.tree.leaves(st_g)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            jax.tree_util.keystr(path)


# the benchmark's tier (dm-writecache: write-only allocation, 50/45%)
_WB_PW = HostCacheSpec(mode="wb", promote="write", wm_hi=0.5, wm_lo=0.45)
_TRIM_LEN = TRIM_QUANTUM + 2048     # one scanned quantum, then 2048 pads


@pytest.fixture(scope="module")
def tail_traces():
    """Per mode, two cells with pad tails of different lengths: hm_0's
    live ops fill the scanned quantum, hm_1's stop at 5,000, so its pads
    start inside the scanned prefix."""
    out = {}
    for mode in ("daily", "bursty"):
        out[mode] = [
            _with_tail(make_trace(name, N_LOGICAL, mode=mode,
                                  capacity_pages=CFG.total_pages),
                       n_live, _TRIM_LEN)
            for name, n_live in (("hm_0", TRIM_QUANTUM), ("hm_1", 5000))]
    return out


class TestTierTrim:
    """A tier fleet's trimmed scan (`fleet._run_fleet_trim`) equals its
    full-length scan bit for bit: the host carry, the device carry and
    the latency."""

    @pytest.mark.parametrize("mode", ["daily", "bursty"])
    @pytest.mark.parametrize("hc,policy", [
        (_WB_PW, "coop"),
        (HostCacheSpec(mode="wb", flush="watermark"), "baseline"),
        (HostCacheSpec(mode="wb", promote="nth", promote_n=2.0), "ips"),
        (HostCacheSpec(mode="wb", flush="idle"), "ips_agc"),
        (HostCacheSpec(mode="wt"), "baseline"),
        (HostCacheSpec(mode="wa"), "ips")],
        ids=lambda v: v.tag if isinstance(v, HostCacheSpec) else v)
    def test_trimmed_tier_fleet_is_bit_identical(self, tail_traces, hc,
                                                 policy, mode):
        traces = tail_traces[mode]
        assert fleet._trim_len(np.stack(
            [t["is_write"] for t in traces])) == TRIM_QUANTUM
        _assert_same_run(
            _tier_fleet(policy, traces, mode, hc, full=True),
            _tier_fleet(policy, traces, mode, hc))

    def test_trimmed_tier_fleet_matches_per_op_cells(self, tail_traces):
        traces = tail_traces["daily"]
        lat_f, st_f = _tier_fleet("coop", traces, "daily", _WB_PW)
        for i, tr in enumerate(traces):
            got = jax.tree.map(lambda x: x[i], (lat_f, st_f))
            _assert_same_run(_run_hc("coop", tr, "daily", _WB_PW), got)

    def test_watermark_latch_settles_on_the_first_pad(self):
        """The last live op arms a flush burst that drains the tier to
        its low watermark: the latch the scanned prefix leaves is 1, and
        the first tail pad, past the scanned prefix, clears it."""
        hc = HostCacheSpec(mode="wb", flush="watermark", sets=8, ways=2,
                           wm_hi=0.5, wm_lo=0.25)      # 8 and 4 lines
        n_fill = TRIM_QUANTUM - 10
        # one read miss allocates a clean line, read hits keep the clock
        # going, 8 write misses arm the latch (2 lines flushed), and one
        # more read hit flushes 2 more: 4 dirty lines, at wm_lo exactly
        lba = np.array([8] * (n_fill + 1) + list(range(8)) + [8], np.int32)
        kind = np.array([0] * (n_fill + 1) + [1] * 8 + [0], np.int32)
        live = {"arrival_ms": 0.5 * np.arange(TRIM_QUANTUM,
                                              dtype=np.float32),
                "lba": lba, "is_write": kind}
        _, st_live = _run_hc("ips", live, "daily", hc)
        assert int(st_live.hostcache.flushing) == 1
        assert int(st_live.hostcache.dirty_n) == 4
        traces = [_with_tail(live, TRIM_QUANTUM, TRIM_QUANTUM + 1024)]
        ref = _tier_fleet("ips", traces, "daily", hc, full=True)
        got = _tier_fleet("ips", traces, "daily", hc)
        assert int(got[1].hostcache.flushing[0]) == 0
        _assert_same_run(ref, got)


class TestTierTrimRouting:
    """The sweep runner sends a tier group with a pad tail to the
    trimmed program, and keeps timeline and wear-tracked tier groups on
    the full-length scan."""

    CSV = os.path.join(os.path.dirname(__file__), "data", "sample_msr.csv")
    MAX_OPS = TRIM_QUANTUM + 4096       # 609 live ops, then pads

    def _sweep(self, pts, **kw):
        from repro import workloads
        from repro.sweep.runner import run_sweep
        from repro.telemetry.spans import Tracer
        timings, tracer = [], Tracer()
        with tracer.activate():
            res = run_sweep(CFG, pts, max_ops=self.MAX_OPS, timings=timings,
                            trace_cache=workloads.TraceCache(use_disk=False),
                            **kw)
        scans = [s["args"] for s in tracer.spans
                 if s["name"] == "device.scan"]
        assert len(timings) == len(scans) == 1
        return res, timings[0], scans[0]

    def test_a_tier_group_with_a_pad_tail_trims(self):
        hc = HostCacheSpec(mode="wb", flush="idle", sets=64)
        pt = SweepPoint(self.CSV, "daily", "ips_agc", hostcache=hc)
        c0 = fleet.compile_count()
        _, row, scan = self._sweep([pt])
        assert fleet.compile_count() == c0 + 1
        assert (row["t_scan"], row["t_len"], row["exec_path"]) == \
            (TRIM_QUANTUM, self.MAX_OPS, "segment")
        assert (scan["t_scan"], scan["t_len"], scan["hostcache"]) == \
            (TRIM_QUANTUM, self.MAX_OPS, hc.tag)
        # the same shapes with other traced knobs compile nothing new
        c1 = fleet.compile_count()
        knobs = SweepPoint(self.CSV, "daily", "ips_agc", hostcache=hc,
                           cache_frac=0.5, idle_threshold_ms=20.0)
        _, row, _ = self._sweep([knobs])
        assert fleet.compile_count() == c1 and row["compiles"] == 0
        assert row["t_scan"] == TRIM_QUANTUM

    @pytest.mark.parametrize("group", ["timeline", "endurance"])
    def test_timeline_and_wear_tier_groups_scan_full_length(self, group):
        from repro.core.ssd.endurance.spec import EnduranceSpec
        hc = HostCacheSpec(mode="wb", flush="idle", sets=64)
        kw, endurance = {}, None
        if group == "timeline":
            kw["timeline_ops"] = 1024
        else:
            endurance = EnduranceSpec()
        pt = SweepPoint(self.CSV, "daily", "ips", hostcache=hc,
                        endurance=endurance)
        _, row, scan = self._sweep([pt], **kw)
        assert row["t_scan"] == row["t_len"] == self.MAX_OPS
        assert row["exec_path"] == "per_op"
        assert scan["t_scan"] == scan["t_len"] == self.MAX_OPS


class TestSpecAndGrid:
    @pytest.mark.parametrize("text,fields,tag", [
        ("mode=wt,sets=64,ways=4,wm_hi=0.9",
         dict(mode="wt", sets=64, ways=4, wm_hi=0.9),
         "wt:watermark:64x4:wm0.9-0.5"),
        ("promote=write,wm_hi=0.5,wm_lo=0.45",
         dict(promote="write", wm_hi=0.5, wm_lo=0.45),
         "wb:watermark:pw:wm0.5-0.45"),
        ("promote=nth,promote_n=3", dict(promote="nth", promote_n=3.0),
         "wb:watermark:p3")])
    def test_parse_round_trip_and_tag(self, text, fields, tag):
        hc = HostCacheSpec.parse(text)
        assert hc == HostCacheSpec(**fields)
        assert hc.tag == tag
        assert HostCacheSpec.parse("sets=64,ways=4").lines == 256
        assert HostCacheSpec.parse("") == HostCacheSpec()
        assert HostCacheSpec().tag == "wb:watermark"
        # the tags of the three promote gates differ: the point key
        # tells them apart
        assert len({HostCacheSpec(promote=p).tag
                    for p in ("always", "nth", "write")}) == 3

    @pytest.mark.parametrize("text", [
        "nope=1", "mode=magic", "sets=abc", "mode", "flush=never"])
    def test_parse_rejects_bad_knobs(self, text):
        with pytest.raises(ValueError):
            HostCacheSpec.parse(text)

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="flush_per_op < sets"):
            HostCacheSpec(sets=2, flush_per_op=2)
        with pytest.raises(ValueError, match="off == omit"):
            HostCacheSpec(mode="off")
        with pytest.raises(ValueError, match="promote='write'"):
            HostCacheSpec(mode="wa", promote="write")

    def test_point_key_carries_hostcache_tag(self):
        hc = HostCacheSpec(mode="wb", flush="idle")
        pt = SweepPoint("flush_burst", "daily", "ips", hostcache=hc)
        assert "hc=wb:idle" in pt.key
        bare = SweepPoint("flush_burst", "daily", "ips")
        assert "hc=" not in bare.key

    def test_hostcache_grid_shape(self):
        pts = hostcache_grid()
        assert pts == named_grid("hostcache")
        assert len(pts) == 40                      # 4 pol x 2 mode x 5 hc
        assert {p.trace for p in pts} == {"flush_burst"}
        off = [p for p in pts if p.hostcache is None]
        assert len(off) == 8                       # paired references
        tags = {p.hostcache.tag for p in pts if p.hostcache is not None}
        assert tags == {"wb:watermark", "wb:idle", "wt:watermark",
                        "wa:watermark"}


class TestSweepAndReport:
    def test_sweep_pairs_and_headline_excludes_host_cells(self):
        from repro.sweep.runner import run_sweep
        hc = HostCacheSpec()
        pts = [SweepPoint("hm_0", "daily", pol, hostcache=h)
               for pol in ("baseline", "ips") for h in (None, hc)]
        res = run_sweep(CFG, pts, max_ops=2048)
        assert set(res) == set(pts)
        for p in pts:
            has_host = "host_hit_rate" in res[p]
            assert has_host == (p.hostcache is not None), p.key
        summ = hostcache_summary(res)
        assert set(summ) == {("daily", "baseline", hc.tag),
                             ("daily", "ips", hc.tag)}
        for row in summ.values():
            assert row["lat_vs_off"] is not None
            assert row["host_dev_write_frac"] < 1.0
        # the headline geomeans stay a device-only story
        gm = policy_geomeans(res)
        assert ("daily", "ips") in gm
        off = {p: v for p, v in res.items() if p.hostcache is None}
        assert gm == policy_geomeans(off)
