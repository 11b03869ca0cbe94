"""Telemetry engine tests (DESIGN.md §11).

The load-bearing contract: the in-scan probe is OBSERVATION ONLY —
enabling `timeline_ops` must leave every latency, counter, and state
field bit-identical to a telemetry-off run, for all paper policies in
both replay modes, single-cell and fleet-batched. On top of that, the
windowed series must conserve: per-window counter deltas sum exactly to
the final counters, windowed write counts match the trace, and the
latency histogram holds every write. Cliff detection, percentile
recovery, span tracing, and the atomic BENCH store ride along as pure
host-side units.
"""
import json
import os
import threading

import numpy as np
import pytest

from repro.configs.ssd_paper import PAPER_SSD
from repro.core.ssd import fleet
from repro.core.ssd.driver import _agc_waste_p
from repro.core.ssd.sim import default_params, run_compressed, run_trace
from repro.core.ssd.workloads import make_trace, stack_traces, truncate_trace
from repro.telemetry import (Tracer, active_tracer, cell_timeline,
                             detect_cliff, event, percentile, series, span,
                             timeline_to_numpy)
from repro.telemetry.probe import LAT_EDGES_MS, n_windows
from repro.workloads.compress import SEG_LANES, TRIM_QUANTUM, compress_ops

CFG = PAPER_SSD.scaled(128)
N_LOGICAL = min(CFG.total_pages, 1 << 16)
MAX_OPS = 8192
WINDOW = 512
POLICIES = ["baseline", "ips", "coop", "ips_agc"]


def _trace(mode, name="hm_0"):
    return truncate_trace(
        make_trace(name, N_LOGICAL, mode=mode,
                   capacity_pages=CFG.total_pages), MAX_OPS)


def _padded_trace(mode, name="hm_0", n_pad=TRIM_QUANTUM):
    """`_trace` + an `ir.pad_ops`-contract tail (constant arrival, lba 0,
    is_write -1) so compression trims and telemetry windows span the
    fixed-point tail replay — the load-bearing segment-telemetry path."""
    tr = _trace(mode, name)
    return {
        "arrival_ms": np.concatenate(
            [tr["arrival_ms"],
             np.full(n_pad, tr["arrival_ms"][-1], np.float32)]),
        "lba": np.concatenate(
            [tr["lba"], np.zeros(n_pad, np.asarray(tr["lba"]).dtype)]),
        "is_write": np.concatenate(
            [tr["is_write"],
             np.full(n_pad, -1, np.asarray(tr["is_write"]).dtype)]),
    }


def _assert_timelines_equal(ref, got, label=""):
    assert got is not None and ref is not None
    for field in ref._fields:
        a, b = getattr(ref, field), getattr(got, field)
        if a is None:
            assert b is None, f"{label}: {field} should be None"
            continue
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"{label}: timeline.{field} mismatch"


@pytest.fixture(scope="module", params=["bursty", "daily"])
def mode(request):
    return request.param


class TestProbeBitIdentity:
    @pytest.mark.parametrize("policy", POLICIES)
    def test_off_vs_on_identical(self, mode, policy):
        """Telemetry on == telemetry off, bit for bit, on every output
        the simulation produces (the probe only APPENDS the timeline)."""
        tr = _trace(mode)
        cl = mode == "bursty"
        lat0, st0 = run_trace(CFG, policy, tr, closed_loop=cl,
                              n_logical=N_LOGICAL)
        lat1, st1 = run_trace(CFG, policy, tr, closed_loop=cl,
                              n_logical=N_LOGICAL, timeline_ops=WINDOW)
        assert np.array_equal(np.asarray(lat0), np.asarray(lat1))
        assert st0.timeline is None and st1.timeline is not None
        for field in st0._fields:
            if field == "timeline":
                continue
            v0 = getattr(st0, field)
            if v0 is None:
                assert getattr(st1, field) is None
                continue
            assert np.array_equal(np.asarray(v0),
                                  np.asarray(getattr(st1, field))), field


class TestWindowConservation:
    def test_counters_and_histogram_conserve(self, mode):
        """Per-window counter deltas telescope exactly to the final
        counters; windowed op/write counts match the trace; the latency
        histogram holds one entry per write; windowed latency sums add
        up to the scan's own latency output."""
        tr = _trace(mode)
        lat, st = run_trace(CFG, "baseline", tr,
                            closed_loop=(mode == "bursty"),
                            n_logical=N_LOGICAL, timeline_ops=WINDOW)
        tl = st.timeline
        is_w = np.asarray(tr["is_write"])
        assert np.array_equal(
            np.asarray(tl.ctr).sum(axis=0).astype(np.float32),
            np.asarray(st.counters))
        assert np.asarray(tl.ops).sum() == (is_w >= 0).sum()
        assert np.asarray(tl.writes).sum() == (is_w == 1).sum()
        assert np.asarray(tl.lat_hist).sum() == (is_w == 1).sum()
        wlat = np.where(is_w == 1, np.asarray(lat), 0.0)
        assert np.isclose(np.asarray(tl.lat_sum).sum(), wlat.sum(),
                          rtol=1e-5)

    def test_fleet_cells_match_single_cell(self, mode):
        """Every fleet cell's timeline == the single-cell run's, leaf
        for leaf (windowing is positional, so stacking is transparent)."""
        names = ("hm_0", "hm_1")
        _, traces = stack_traces(names, N_LOGICAL, mode=mode,
                                 capacity_pages=CFG.total_pages,
                                 max_ops=MAX_OPS)
        waste = [_agc_waste_p(n) for n in names]
        params = fleet.stack_params(
            [default_params(CFG, "ips", w) for w in waste])
        cl = mode == "bursty"
        lat_f, st_f = fleet.run_fleet(CFG, "ips", fleet.stack_ops(traces),
                                      params, closed_loop=cl,
                                      n_logical=N_LOGICAL,
                                      timeline_ops=WINDOW)
        tl_np = timeline_to_numpy(st_f.timeline)
        for i, (tr, w) in enumerate(zip(traces, waste)):
            lat_r, st_r = run_trace(CFG, "ips", tr, closed_loop=cl,
                                    n_logical=N_LOGICAL, waste_p=w,
                                    timeline_ops=WINDOW)
            assert np.array_equal(np.asarray(lat_f[i]), np.asarray(lat_r))
            ref = timeline_to_numpy(st_r.timeline)
            cell = cell_timeline(tl_np, i)
            for k in ref:
                if k == "window_ops":
                    assert int(cell[k]) == int(ref[k])
                    continue
                assert np.array_equal(cell[k], ref[k]), k

    def test_window_count_shape(self):
        tr = _trace("bursty")
        t_len = int(np.asarray(tr["lba"]).shape[0])
        _, st = run_trace(CFG, "baseline", tr, closed_loop=True,
                          n_logical=N_LOGICAL, timeline_ops=WINDOW)
        assert np.asarray(st.timeline.ops).shape == \
            (n_windows(t_len, WINDOW),)


class TestSeries:
    def test_series_schema_and_percentiles(self):
        tr = _trace("bursty")
        _, st = run_trace(CFG, "baseline", tr, closed_loop=True,
                          n_logical=N_LOGICAL, timeline_ops=WINDOW)
        s = series(timeline_to_numpy(st.timeline))
        for k in ("window_ops", "n_windows", "ops", "writes",
                  "lat_mean_ms", "lat_p50_ms", "lat_p99_ms", "occ_frac",
                  "free_frac", "waf", "idle_ms", "t_end_ms", "host_w",
                  "slc_w", "tlc_w", "rp_w", "mig_w", "erases", "cliff"):
            assert k in s, k
        assert s["n_windows"] == len(s["ops"]) > 0
        # percentiles bracket the mean where defined
        for p50, p99, mean in zip(s["lat_p50_ms"], s["lat_p99_ms"],
                                  s["lat_mean_ms"]):
            if mean is not None:
                assert p50 <= p99
        # occupancy is a fraction
        occ = [v for v in s["occ_frac"] if v is not None]
        assert occ and all(0.0 <= v <= 1.0 for v in occ)

    def test_percentile_recovers_point_mass(self):
        """A histogram with all mass in one bucket returns a value inside
        that bucket for every quantile."""
        hist = np.zeros((1, LAT_EDGES_MS.size + 1))
        hist[0, 4] = 100.0                  # [edges[3], edges[4])
        for q in (0.1, 0.5, 0.99):
            v = percentile(hist, LAT_EDGES_MS, q)[0]
            assert LAT_EDGES_MS[3] <= v <= LAT_EDGES_MS[4]
        assert np.isnan(percentile(np.zeros((1, hist.shape[1])),
                                   LAT_EDGES_MS, 0.5)[0])


class TestCliffDetection:
    def _series(self, steady, cliff_at, ratio, n=40, sustain_n=10):
        lat = np.full(n, steady)
        lat[cliff_at:cliff_at + sustain_n] = steady * ratio
        return lat, np.full(n, 100.0)

    def test_detects_sustained_jump(self):
        lat, w = self._series(0.6, 20, 3.0)
        c = detect_cliff(lat, w, window_ops=512)
        assert c["detected"] and c["window"] == 20
        assert c["ratio"] == pytest.approx(3.0, rel=0.05)
        assert c["time_to_cliff_ops"] == 20 * 512

    def test_ignores_single_window_spike(self):
        lat, w = self._series(0.6, 20, 3.0, sustain_n=1)
        assert not detect_cliff(lat, w)["detected"]

    def test_flat_series_has_no_cliff(self):
        lat, w = self._series(0.6, 0, 1.0)
        c = detect_cliff(lat, w)
        assert not c["detected"]
        assert c["steady_lat_ms"] == pytest.approx(0.6)

    def test_early_cliff_does_not_inflate_steady(self):
        """A cliff in the earliest windows must not drag the steady
        reference up with it (steady is clamped by the p25 of all
        windows)."""
        lat = np.full(40, 0.6)
        lat[2:8] = 2.4
        c = detect_cliff(lat, np.full(40, 100.0))
        assert c["detected"] and c["window"] == 2
        assert c["steady_lat_ms"] == pytest.approx(0.6)

    def test_recovery_slope_sign(self):
        lat = np.full(40, 0.6)
        lat[10:] = np.linspace(3.0, 1.3, 30) * 0.6
        c = detect_cliff(lat, np.full(40, 100.0))
        assert c["detected"] and c["recovery_slope"] < 0


class TestSpans:
    def test_span_nesting_and_totals(self):
        tr = Tracer()
        with tr.activate():
            assert active_tracer() is tr
            with span("outer", "test", k=1):
                with span("inner", "test"):
                    pass
            event("marker", "test", note="x")
        assert active_tracer() is None
        spans = tr.to_json()
        names = [s["name"] for s in spans]
        assert names == ["outer", "inner", "marker"]  # opened in order
        outer = spans[names.index("outer")]
        inner = spans[names.index("inner")]
        assert inner["depth"] == outer["depth"] + 1
        assert inner["parent"] == names.index("outer")
        assert inner["dur_s"] <= outer["dur_s"]
        assert tr.totals()["outer"]["count"] == 1

    def test_span_without_tracer_still_times(self):
        """Module-level span() must yield a record with dur_s filled even
        when no tracer is active (callers read rec["dur_s"])."""
        with span("orphan", "test") as rec:
            pass
        assert rec["dur_s"] >= 0.0

    def test_record_lies_on_no_host_stack(self):
        """`Tracer.record` adds a span with explicit clock readings at
        depth -1, and host spans opened after it keep their parents."""
        import time
        tr = Tracer()
        with tr.activate():
            with span("outer", "test"):
                t0 = time.perf_counter()
                rec = tr.record("device.x", "device", t0, t0 + 0.5, k=2)
                with span("inner", "test"):
                    pass
        names = [s["name"] for s in tr.spans]
        assert names == ["outer", "device.x", "inner"]
        assert rec["depth"] == -1 and rec["parent"] is None
        assert rec["dur_s"] == pytest.approx(0.5)
        assert rec["args"] == {"k": 2}
        inner = tr.spans[2]
        assert inner["parent"] == 0 and inner["depth"] == 1
        assert rec["t0_s"] >= tr.spans[0]["t0_s"]

    def test_chrome_trace_puts_device_spans_on_their_own_track(
            self, tmp_path):
        from repro.telemetry import chrome_trace
        tr = Tracer()
        with tr.activate():
            with span("sweep.run", "sweep"):
                pass
        tr.record("device.scan", "device", tr._t0, tr._t0 + 1.0)
        path = chrome_trace(tr.to_json(), str(tmp_path / "t.json"))
        with open(path) as f:
            tids = {e["name"]: e["tid"] for e in json.load(f)["traceEvents"]}
        assert tids == {"sweep.run": 0, "device.scan": 1}


def _sweep_points():
    from repro.sweep.grid import SweepPoint
    return [SweepPoint(trace=t, mode="daily", policy=p, seed=3)
            for p in ("baseline", "ips") for t in ("hm_0", "hm_1")]


def _sweep(tracer=None, progress=None):
    """A 2-fleet daily sweep at a CPU size, traced when `tracer` is
    given; returns (results, timings)."""
    import contextlib
    from repro import workloads
    from repro.sweep.runner import run_sweep
    timings = []
    with (tracer.activate() if tracer else contextlib.nullcontext()):
        res = run_sweep(CFG, _sweep_points(), max_ops=2048,
                        timings=timings, progress=progress,
                        trace_cache=workloads.TraceCache(use_disk=False))
    return res, timings


@pytest.fixture(scope="module")
def sweeps():
    """An untraced sweep, then two traced ones, and the live thread
    counts seen from inside the untraced and the first traced sweep."""
    live = {"untraced": [], "traced": []}
    before = threading.active_count()
    plain, _ = _sweep(progress=lambda _: live["untraced"].append(
        threading.active_count()))
    import jax
    traces = []

    def on_duration(event, _secs, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            traces.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        first = Tracer()
        traced, timings = _sweep(first, progress=lambda _: live[
            "traced"].append(threading.active_count()))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    again = Tracer()
    _sweep(again)
    return {"plain": plain, "traced": traced, "timings": timings,
            "first": first, "first_traces": len(traces), "again": again,
            "live": live, "before": before,
            "after": threading.active_count()}


class TestSweepDeviceSpans:
    """The sweep runner's device completion spans and phase counters
    (DESIGN.md §13)."""

    def test_one_scan_and_one_tail_per_fleet_in_dispatch_order(
            self, sweeps):
        spans = sweeps["first"].spans
        run = [s for s in spans if s["name"] == "sweep.run"]
        assert len(run) == 1
        lo, hi = run[0]["t0_s"], run[0]["t0_s"] + run[0]["dur_s"]
        dispatched = [s["args"]["group"] for s in spans
                      if s["name"] == "sweep.dispatch"]
        dev = sorted((s for s in spans if s["cat"] == "device"),
                     key=lambda s: s["t0_s"])
        assert [s["name"] for s in dev] == \
            ["device.scan", "device.tail"] * len(dispatched)
        assert [s["args"]["group"] for s in dev[::2]] == dispatched
        assert [s["args"]["group"] for s in dev[1::2]] == dispatched
        for a, b in zip(dev, dev[1:]):
            assert a["t0_s"] + a["dur_s"] <= b["t0_s"] + 1e-9
        for s in dev:
            assert s["dur_s"] >= 0.0 and s["depth"] == -1
            assert lo <= s["t0_s"] and s["t0_s"] + s["dur_s"] <= hi
        t_rows = {g["policies"]: g for g in sweeps["timings"]}
        for s in dev[::2]:
            row = t_rows[s["args"]["group"]]
            assert (s["args"]["t_scan"], s["args"]["t_len"],
                    s["args"]["exec_path"]) == \
                (row["t_scan"], row["t_len"], row["exec_path"])

    def test_phase_spans_nest_under_the_run(self, sweeps):
        spans = sweeps["first"].spans
        run_idx = [i for i, s in enumerate(spans)
                   if s["name"] == "sweep.run"][0]
        for s in spans:
            if s["name"] in ("sweep.group", "sweep.dispatch",
                             "sweep.block"):
                assert s["parent"] == run_idx
        # every trace is built in the grouping loop; later lookups are
        # instant cache-hit events
        builds = [s for s in spans if s["cat"] == "workload"
                  and s["dur_s"] > 0
                  and spans[s["parent"]]["cat"] != "workload"]
        group = [i for i, s in enumerate(spans)
                 if s["name"] == "sweep.group"][0]
        assert builds and all(s["parent"] == group for s in builds)

    def test_no_tracer_starts_no_thread_and_changes_no_result(self, sweeps):
        before = sweeps["before"]
        assert sweeps["live"]["untraced"] and \
            set(sweeps["live"]["untraced"]) == {before}
        # the watcher is alive while a traced sweep dispatches, and gone
        # after it returns
        assert set(sweeps["live"]["traced"]) == {before + 1}
        assert sweeps["after"] == before
        plain, traced = sweeps["plain"], sweeps["traced"]
        assert plain.keys() == traced.keys()
        for pt in plain:
            assert plain[pt] == traced[pt], pt

    def test_counters_count_traces_and_repeat_without_compiles(
            self, sweeps):
        def phases(tracer):
            return [s for s in tracer.spans if s["name"] in
                    ("sweep.group", "sweep.dispatch", "sweep.block")]
        first = phases(sweeps["first"])
        assert all("jaxpr_traces" in s["args"] and
                   "backend_compiles" in s["args"] for s in first)
        # the phases hold every trace the sweep makes
        assert sum(s["args"]["jaxpr_traces"] for s in first) == \
            sweeps["first_traces"] > 0
        again = [s for s in sweeps["again"].spans
                 if s["name"] == "sweep.dispatch"]
        assert again and all(s["args"]["compiles"] == 0 for s in again)
        assert all(s["args"]["backend_compiles"] == 0 for s in again)


    def test_tier_fleets_name_their_tier_and_sub_ops(self, sweeps):
        """A tier fleet's `sweep.dispatch` and `device.scan` spans carry
        its spec's tag and its device sub-op slots a step (2 +
        flush_per_op); other fleets carry None and 1."""
        from repro import workloads
        from repro.hostcache import HostCacheSpec
        from repro.sweep.grid import SweepPoint
        from repro.sweep.runner import run_sweep
        hc = HostCacheSpec(promote="write", flush_per_op=3)
        pts = [SweepPoint(trace="hm_0", mode="daily", policy="ips",
                          seed=3, hostcache=h) for h in (hc, None)]
        tracer = Tracer()
        with tracer.activate():
            run_sweep(CFG, pts, max_ops=1024,
                      trace_cache=workloads.TraceCache(use_disk=False))
        got = [(s["name"], s["args"]["hostcache"], s["args"]["sub_ops"])
               for s in tracer.spans
               if s["name"] in ("sweep.dispatch", "device.scan")]
        assert len(got) == 4
        assert set(got) == {("device.scan", None, 1),
                            ("device.scan", hc.tag, 5),
                            ("sweep.dispatch", None, 1),
                            ("sweep.dispatch", hc.tag, 5)}
        # the paper fleets of the module's sweeps: no tier
        for s in sweeps["first"].spans:
            if s["name"] in ("sweep.dispatch", "device.scan"):
                assert (s["args"]["hostcache"], s["args"]["sub_ops"]) == \
                    (None, 1)


class TestSegmentWindows:
    """Segment-aware telemetry (DESIGN.md §13): the compressed segment
    executor's boundary snapshots must re-expand into the SAME per-window
    series the per-op probe produces — bit-identical, field for field —
    so cliff detection runs at compressed speed."""

    @pytest.mark.parametrize("policy", POLICIES)
    def test_segment_vs_per_op_bit_identical(self, mode, policy):
        """Every WindowedTimeline field (incl. the latency histogram and
        the counter deltas behind windowed WAF), the per-op latency and
        the final state: segment path == per-op path, bit for bit.
        Cliff detection over the two window sets is therefore identical
        too (asserted on the derived series)."""
        tr = _padded_trace(mode)
        comp = compress_ops(tr)
        assert comp.n_pad > 0          # tail-replay windows load-bearing
        cl = mode == "bursty"
        lat_r, st_r = run_trace(CFG, policy, tr, closed_loop=cl,
                                n_logical=N_LOGICAL, timeline_ops=WINDOW)
        lat_c, st_c = run_compressed(CFG, policy, comp, closed_loop=cl,
                                     n_logical=N_LOGICAL,
                                     timeline_ops=WINDOW)
        assert np.array_equal(np.asarray(lat_r), np.asarray(lat_c))
        _assert_timelines_equal(st_r.timeline, st_c.timeline,
                                f"{policy}/{mode}")
        for field in st_r._fields:
            if field == "timeline":
                continue
            v = getattr(st_r, field)
            if v is None:
                assert getattr(st_c, field) is None
                continue
            assert np.array_equal(np.asarray(v),
                                  np.asarray(getattr(st_c, field))), field
        s_r = series(timeline_to_numpy(st_r.timeline))
        s_c = series(timeline_to_numpy(st_c.timeline))
        assert s_c["cliff"] == s_r["cliff"]

    def test_segment_window_conservation(self, mode):
        """Summing the segment-produced per-window counter deltas
        reproduces the final CTR counters EXACTLY (telescoping boundary
        snapshots), mirroring the per-op conservation test — including
        the windows recovered from the fixed-point tail replay."""
        tr = _padded_trace(mode)
        comp = compress_ops(tr)
        _, st = run_compressed(CFG, "baseline", comp,
                               closed_loop=(mode == "bursty"),
                               n_logical=N_LOGICAL, timeline_ops=WINDOW)
        tl = st.timeline
        is_w = np.asarray(tr["is_write"])
        assert np.array_equal(
            np.asarray(tl.ctr).sum(axis=0).astype(np.float32),
            np.asarray(st.counters))
        assert np.asarray(tl.ops).sum() == (is_w >= 0).sum()
        assert np.asarray(tl.writes).sum() == (is_w == 1).sum()
        assert np.asarray(tl.lat_hist).sum() == (is_w == 1).sum()

    def test_window_must_align_with_segment_lanes(self):
        """Segment snapshots exist only at segment ends: a window size
        that is not a SEG_LANES multiple must be rejected loudly, not
        silently misaligned."""
        comp = compress_ops(_padded_trace("bursty"))
        with pytest.raises(ValueError, match=f"% {SEG_LANES}"):
            run_compressed(CFG, "baseline", comp, closed_loop=True,
                           n_logical=N_LOGICAL,
                           timeline_ops=WINDOW + 1)

    def test_fleet_trim_timeline_identity(self):
        """The trimmed fleet fast path with telemetry on == the full
        per-op fleet, per cell and leaf for leaf (prefix rows + tail
        snapshot windows; no lane-alignment constraint on this path —
        hence the deliberately odd window size)."""
        traces = [_padded_trace("daily", n) for n in ("hm_0", "hm_1")]
        ops = fleet.stack_ops(traces)
        params = fleet.stack_params(
            [default_params(CFG, "ips") for _ in traces])
        win = 480                      # NOT a SEG_LANES multiple: allowed
        lat_f, st_f = fleet.run_fleet(CFG, "ips", ops, params,
                                      closed_loop=False,
                                      n_logical=N_LOGICAL,
                                      timeline_ops=win)
        lat_t, st_t = fleet.run_fleet(CFG, "ips", ops, params,
                                      closed_loop=False,
                                      n_logical=N_LOGICAL,
                                      timeline_ops=win, trim_pads=True)
        assert np.array_equal(np.asarray(lat_f), np.asarray(lat_t))
        _assert_timelines_equal(st_f.timeline, st_t.timeline, "fleet")
        for field in st_f._fields:
            if field == "timeline":
                continue
            v = getattr(st_f, field)
            if v is None:
                assert getattr(st_t, field) is None
                continue
            assert np.array_equal(np.asarray(v),
                                  np.asarray(getattr(st_t, field))), field


class TestHistory:
    """BENCH_history.json perf-regression ledger (DESIGN.md §13) —
    stdlib-only, atomic, git-SHA-keyed."""

    def _rec(self, tmp_path, ops, gm=1.0, config="ci:quick"):
        from repro.telemetry import history
        return history.append_record(
            "sweep", config, directory=str(tmp_path), ops_per_s=ops,
            geomeans={"daily/ips/wa_paper": gm}, compiles=3,
            shard_skipped=0, git_sha="deadbeef")

    def test_append_load_roundtrip(self, tmp_path):
        from repro.telemetry import history
        rec = self._rec(tmp_path, 1000.0)
        assert rec["git_sha"] == "deadbeef" and rec["kind"] == "sweep"
        doc = history.load_history(str(tmp_path))
        assert doc["schema_version"] == 1
        assert [r["ops_per_s"] for r in doc["records"]] == [1000.0]
        self._rec(tmp_path, 1100.0)
        doc = history.load_history(str(tmp_path))
        assert len(doc["records"]) == 2   # append-only: nothing rewritten
        assert doc["records"][0]["ops_per_s"] == 1000.0

    def test_concurrent_appends_lose_nothing(self, tmp_path):
        from repro.telemetry import history
        errs = []

        def add(n):
            try:
                history.append_record("bench_step", "c", ops_per_s=n,
                                      directory=str(tmp_path),
                                      git_sha="x")
            except Exception as e:      # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=add, args=(float(n),))
                   for n in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        recs = history.load_history(str(tmp_path))["records"]
        assert sorted(r["ops_per_s"] for r in recs) == \
            [float(n) for n in range(8)]
        leftovers = [f for f in os.listdir(tmp_path)
                     if f.endswith(".tmp")]
        assert leftovers == []

    def test_injected_2x_slowdown_caught(self, tmp_path):
        from repro.telemetry import history
        for _ in range(3):
            self._rec(tmp_path, 1000.0)
        recs = history.load_history(str(tmp_path))["records"]
        assert history.check_regression(recs) == []   # steady: passes
        self._rec(tmp_path, 500.0)                    # injected 2x slower
        recs = history.load_history(str(tmp_path))["records"]
        failures = history.check_regression(recs)
        assert len(failures) == 1 and "throughput" in failures[0]
        # 10% down is inside the 20% gate
        history.append_record("sweep", "tp", directory=str(tmp_path),
                              ops_per_s=1000.0, git_sha="x")
        history.append_record("sweep", "tp", directory=str(tmp_path),
                              ops_per_s=900.0, git_sha="x")
        recs = [r for r in history.load_history(str(tmp_path))["records"]
                if r["config"] == "tp"]
        assert history.check_regression(recs) == []

    def test_any_geomean_drift_fails(self, tmp_path):
        from repro.telemetry import history
        self._rec(tmp_path, 1000.0, gm=0.53)
        self._rec(tmp_path, 1000.0, gm=0.530001)      # tiny, still drift
        recs = history.load_history(str(tmp_path))["records"]
        failures = history.check_regression(recs)
        assert len(failures) == 1 and "drifted" in failures[0]

    def test_series_isolation_and_first_run(self, tmp_path):
        """Different (kind, config) series never compare; a lone first
        record seeds its baseline and passes."""
        from repro.telemetry import history
        self._rec(tmp_path, 1000.0, config="grid_a")
        self._rec(tmp_path, 100.0, config="grid_b")   # 10x apart: fine
        recs = history.load_history(str(tmp_path))["records"]
        assert history.check_regression(recs) == []

    def test_cli_check_exit_codes(self, tmp_path, capsys):
        from repro.telemetry.history import _main
        assert _main(["--path", str(tmp_path), "--check"]) == 0
        for _ in range(2):
            self._rec(tmp_path, 1000.0)
        assert _main(["--path", str(tmp_path), "--check"]) == 0
        self._rec(tmp_path, 400.0)
        assert _main(["--path", str(tmp_path), "--check"]) == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out


class TestStoreAtomicity:
    def test_save_bench_atomic_and_concurrent(self, tmp_path):
        """Concurrent writers to one BENCH path: the survivor must be a
        complete, parseable document (temp + atomic rename, no torn
        JSON), and no temp droppings remain."""
        from repro.sweep.store import load_bench, save_bench
        payload = {"results": {f"k{i}": {"v": i} for i in range(200)}}
        errs = []

        def write(n):
            try:
                save_bench("atomic", {**payload, "writer": n},
                           directory=str(tmp_path))
            except Exception as e:      # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=write, args=(n,))
                   for n in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        doc = load_bench(str(tmp_path / "BENCH_atomic.json"))
        assert doc["writer"] in range(8)
        assert len(doc["results"]) == 200
        assert doc["meta"]["schema_version"] >= 1
        assert "git_sha" in doc["meta"]
        leftovers = [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
        assert leftovers == []
