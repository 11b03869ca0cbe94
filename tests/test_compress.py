"""Step-engine tests (DESIGN.md §12): event compression, the
compressed-segment executor, the fused Pallas step kernel, packed
SimState, and fleet pad trimming.

The load-bearing contract extends the tests/golden_sim.py chain: the
per-op scan is bit-identical to the vendored golden monolith
(tests/test_policies.py), and everything here is bit-identical to the
per-op scan — every SimState leaf and the full latency array — so each
fast path is transitively certified against the seed:

  golden monolith == per-op scan == compressed segments == fused kernel
                                 == packed carry == trimmed fleet
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.workloads as wl
from repro.configs.ssd_paper import PAPER_SSD
from repro.core.ssd import fleet
from repro.core.ssd.sim import (default_params, run_compressed, run_trace,
                                summarize)
from repro.core.ssd.policies.state import can_pack, init_state
from repro.workloads.compress import (SEG_LANES, TRIM_QUANTUM,
                                      compress_ops, n_live_ops)

CFG = PAPER_SSD.scaled(128)
N_LOGICAL = min(CFG.total_pages, 1 << 16)
PAPER_POLICIES = ("baseline", "ips", "ips_agc", "coop")
MAX_OPS = 8192          # truncated traces; the step has no length
#                         dependence, so full-scan equivalence is implied


def _assert_states_equal(ref, got, label):
    for field in ref._fields:
        ref_v = getattr(ref, field)
        got_v = getattr(got, field)
        if ref_v is None:
            assert got_v is None, f"{label}: {field} should be None"
            continue
        assert np.array_equal(np.asarray(ref_v), np.asarray(got_v)), \
            f"{label}: state.{field} mismatch"


def _with_pad_tail(ops, n_pad):
    """Append an `ir.pad_ops`-contract tail (constant arrival, lba 0,
    is_write -1) so the trim + fixed-point-replay path is exercised."""
    out = dict(ops)
    out["arrival_ms"] = np.concatenate(
        [ops["arrival_ms"],
         np.full(n_pad, ops["arrival_ms"][-1], np.float32)])
    out["lba"] = np.concatenate(
        [ops["lba"], np.zeros(n_pad, ops["lba"].dtype)])
    out["is_write"] = np.concatenate(
        [ops["is_write"], np.full(n_pad, -1, ops["is_write"].dtype)])
    if "req_id" in out:
        out["req_id"] = np.concatenate(
            [ops["req_id"], np.full(n_pad, -1, ops["req_id"].dtype)])
    return out


def _fixture_ops(spec):
    ops = wl.build_ops(spec, N_LOGICAL, capacity_pages=CFG.total_pages)
    ops = wl.truncate_trace(ops, MAX_OPS)
    # tail pads make trim + replay load-bearing (truncation strips the
    # natural tail, which would leave the fixed-point loop untested)
    return _with_pad_tail(ops, TRIM_QUANTUM)


@pytest.fixture(scope="module", params=["hm_0", "adv_ips_base"])
def trace_ops(request):
    return request.param, _fixture_ops(request.param)


class TestCompressOps:
    def test_shapes_and_trim(self, trace_ops):
        _, ops = trace_ops
        comp = compress_ops(ops)
        t_len = len(ops["arrival_ms"])
        assert comp.t_len == t_len
        assert comp.t_trim % TRIM_QUANTUM == 0
        assert comp.t_trim + comp.n_pad == t_len
        assert comp.n_pad == TRIM_QUANTUM          # the appended tail
        s, k = comp.segs["lba"].shape
        assert k == SEG_LANES and s * k == comp.t_trim
        for key in ("arrival_ms", "lba", "is_write", "src", "scat_lba"):
            assert comp.segs[key].shape == (s, k)

    def test_hazard_plan_is_exact(self, trace_ops):
        """`src` points at the immediately-preceding same-lba lane of the
        same segment; `scat_lba` keeps exactly each (segment, lba)'s
        final lane."""
        _, ops = trace_ops
        comp = compress_ops(ops)
        lba = comp.segs["lba"]
        src = comp.segs["src"]
        scat = comp.segs["scat_lba"]
        s_cnt, k = lba.shape
        for s in range(min(s_cnt, 64)):            # spot-check a prefix
            last = {}
            for i in range(k):
                a = int(lba[s, i])
                assert src[s, i] == last.get(a, -1)
                last[a] = i
            finals = set(last.items())
            for i in range(k):
                if (int(lba[s, i]), i) in finals:
                    assert scat[s, i] == lba[s, i]
                else:
                    assert scat[s, i] >= N_LOGICAL

    def test_interior_pad_rejected(self):
        is_write = np.array([1, 0, -1, 1, -1, -1])
        with pytest.raises(ValueError, match="contiguous tail"):
            n_live_ops(is_write)
        assert n_live_ops(np.array([1, 0, -1, -1])) == 2
        assert n_live_ops(np.array([-1, -1])) == 0


class TestCompressedBitIdentity:
    @pytest.mark.parametrize("mode", ["daily", "bursty"])
    @pytest.mark.parametrize("policy", PAPER_POLICIES)
    def test_all_paper_policies(self, trace_ops, policy, mode):
        name, ops = trace_ops
        closed = mode == "bursty"
        params = default_params(CFG, policy, 0.0)
        lat_r, st_r = run_trace(CFG, policy, ops, closed_loop=closed,
                                n_logical=N_LOGICAL, params=params)
        comp = compress_ops(ops)
        lat_c, st_c = run_compressed(CFG, policy, comp, closed_loop=closed,
                                     n_logical=N_LOGICAL, params=params)
        label = f"{name}/{mode}/{policy}"
        assert np.array_equal(np.asarray(lat_r), np.asarray(lat_c)), \
            f"{label}: latency mismatch"
        _assert_states_equal(st_r, st_c, label)

    def test_packed_round_trip(self, trace_ops):
        """int16-packed carry: values bit-identical, summaries (the
        float32-observable totals) bit-identical, dtypes restored."""
        name, ops = trace_ops
        params = default_params(CFG, "ips_agc", 0.0)
        assert can_pack(CFG, N_LOGICAL, params)
        comp = compress_ops(ops)
        lat_u, st_u = run_compressed(CFG, "ips_agc", comp,
                                     closed_loop=False,
                                     n_logical=N_LOGICAL, params=params)
        lat_p, st_p = run_compressed(CFG, "ips_agc", comp,
                                     closed_loop=False,
                                     n_logical=N_LOGICAL, params=params,
                                     packed=True)
        assert np.array_equal(np.asarray(lat_u), np.asarray(lat_p))
        for field in st_u._fields:
            u, p = getattr(st_u, field), getattr(st_p, field)
            if u is None:
                continue
            assert np.array_equal(np.asarray(u), np.asarray(p)), \
                f"packed {field} values differ"
        for f in ("slc_used", "rp_done", "trad_used", "valid_mig",
                  "epoch"):
            assert getattr(st_p, f).dtype == jnp.int16, f
        s_u = summarize(lat_u, ops, st_u)
        s_p = summarize(lat_p, ops, st_p)
        for k in s_u:
            assert np.array_equal(np.asarray(s_u[k]),
                                  np.asarray(s_p[k])), k

    def test_endurance_rejected(self):
        params = default_params(CFG, "ips_raro", 0.0)
        assert params.endurance is not None
        comp = compress_ops(_fixture_ops("hm_0"))
        with pytest.raises(ValueError, match="endurance"):
            run_compressed(CFG, "ips_raro", comp, closed_loop=False,
                           n_logical=N_LOGICAL, params=params)


class TestFusedKernel:
    """`interpret=True` equivalence of the Pallas kernel against the
    engine's jnp segment executor (the CI kernel gate). Small segments
    keep the interpreter affordable; the kernel body has no
    shape-dependent control flow beyond the loop bounds."""

    @pytest.mark.parametrize("mode", ["daily", "bursty"])
    @pytest.mark.parametrize("policy", PAPER_POLICIES)
    def test_interpret_matches_ref(self, policy, mode):
        from repro.kernels.ssd_step.ops import run_segments_fused
        from repro.kernels.ssd_step.ref import run_segments_ref
        ops = wl.truncate_trace(
            wl.build_ops("hm_0", N_LOGICAL,
                         capacity_pages=CFG.total_pages), 1024)
        comp = compress_ops(ops, lanes=8, quantum=64)
        closed = mode == "bursty"
        params = default_params(CFG, policy, 0.0)
        st0 = init_state(CFG, N_LOGICAL)
        segs_j = {k: jnp.asarray(v) for k, v in comp.segs.items()}
        lat_r, (red_r, loc_r, lep_r) = run_segments_ref(
            CFG, policy, segs_j, st0, closed_loop=closed, params=params)
        lat_k, (red_k, loc_k, lep_k) = run_segments_fused(
            CFG, policy, comp.segs, st0, closed_loop=closed,
            params=params, interpret=True)
        assert np.array_equal(np.asarray(lat_r), np.asarray(lat_k))
        assert loc_k.dtype == loc_r.dtype
        assert lep_k.dtype == lep_r.dtype
        assert np.array_equal(np.asarray(loc_r), np.asarray(loc_k))
        assert np.array_equal(np.asarray(lep_r), np.asarray(lep_k))
        for field in red_r._fields:
            assert np.array_equal(
                np.asarray(getattr(red_r, field)),
                np.asarray(getattr(red_k, field))), \
                f"{policy}/{mode}: Reduced.{field} mismatch"

    def test_packed_state_round_trips(self):
        from repro.kernels.ssd_step.ops import run_segments_fused
        from repro.kernels.ssd_step.ref import run_segments_ref
        ops = wl.truncate_trace(
            wl.build_ops("hm_0", N_LOGICAL,
                         capacity_pages=CFG.total_pages), 512)
        comp = compress_ops(ops, lanes=8, quantum=64)
        params = default_params(CFG, "ips_agc", 0.0)
        st0 = init_state(CFG, N_LOGICAL, packed=True)
        segs_j = {k: jnp.asarray(v) for k, v in comp.segs.items()}
        lat_r, (red_r, _, _) = run_segments_ref(
            CFG, "ips_agc", segs_j, st0, closed_loop=False, params=params)
        lat_k, (red_k, _, _) = run_segments_fused(
            CFG, "ips_agc", comp.segs, st0, closed_loop=False,
            params=params, interpret=True)
        assert np.array_equal(np.asarray(lat_r), np.asarray(lat_k))
        for field in red_r._fields:
            r, k = getattr(red_r, field), getattr(red_k, field)
            assert k.dtype == r.dtype, field
            assert np.array_equal(np.asarray(r), np.asarray(k)), field

    def test_endurance_rejected(self):
        from repro.kernels.ssd_step.kernel import run_segments_kernel
        params = default_params(CFG, "ips_raro", 0.0)
        comp = compress_ops(_fixture_ops("hm_0"))
        with pytest.raises(ValueError, match="per-op"):
            run_segments_kernel(CFG, "ips_raro", comp.segs,
                                init_state(CFG, N_LOGICAL),
                                closed_loop=False, params=params)


class TestLiveMask:
    def test_dead_lane_is_noop(self):
        """The core's `live` hook: a dead lane returns every carry leaf
        and residency value unchanged (what makes segment padding
        provably safe)."""
        from repro.core.ssd.policies.engine import (_build_core,
                                                    reduced_of)
        from repro.core.ssd.policies.registry import resolve_spec
        params = default_params(CFG, "ips_agc", 0.0)
        core = _build_core(CFG, resolve_spec("ips_agc"),
                           closed_loop=False, params=params)
        st0 = init_state(CFG, N_LOGICAL)
        red0 = reduced_of(st0)
        op = {"arrival_ms": jnp.float32(5.0), "lba": jnp.int32(17),
              "is_write": jnp.int32(1)}
        red_live, out_live = core(red0, op, st0.loc[17], st0.loc_ep[17],
                                  live=jnp.bool_(True))
        red_dead, out_dead = core(red0, op, st0.loc[17], st0.loc_ep[17],
                                  live=jnp.bool_(False))
        # live=True matches the unmasked path exactly
        red_ref, out_ref = core(red0, op, st0.loc[17], st0.loc_ep[17])
        for a, b in zip(red_live, red_ref):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(out_live.latency),
                              np.asarray(out_ref.latency))
        # live=False leaves everything untouched
        for a, b in zip(red_dead, red0):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert float(out_dead.latency) == 0.0
        assert int(out_dead.loc_val) == int(st0.loc[17])
        assert int(out_dead.loc_ep_val) == int(st0.loc_ep[17])


class TestFleetTrimAndPack:
    def test_trim_and_pack_bit_identical(self):
        names = ("hm_0", "adv_ips_base")
        traces = [_fixture_ops(n) for n in names]
        params = fleet.stack_params(
            [default_params(CFG, "ips_agc", 0.0) for _ in names])
        ops = fleet.stack_ops(traces)
        t_len = ops["lba"].shape[1]
        t_trim = fleet._trim_len(np.asarray(ops["is_write"]))
        lat_ref, st_ref = fleet.run_fleet(
            CFG, "ips_agc", ops, params, closed_loop=False,
            n_logical=N_LOGICAL, plan=fleet.FleetPlan(t_len, False))
        for trim in (True, False):
            lat_t, st_t = fleet.run_fleet(
                CFG, "ips_agc", fleet.stack_ops(traces), params,
                closed_loop=False, n_logical=N_LOGICAL,
                plan=fleet.FleetPlan(t_trim if trim else t_len, True))
            assert np.array_equal(np.asarray(lat_ref), np.asarray(lat_t))
            _assert_states_equal(st_ref, st_t,
                                 f"fleet trim={trim} packed")

    @pytest.mark.parametrize("kind,tail,timeline,t_scan,packed", [
        ("paper", True, None, TRIM_QUANTUM, True),
        ("paper", False, None, 2 * TRIM_QUANTUM, True),
        ("endurance", True, None, 2 * TRIM_QUANTUM, True),
        ("tier", True, None, TRIM_QUANTUM, False),
        ("tier", True, 1024, 2 * TRIM_QUANTUM, False),
        ("paper", True, 1024, TRIM_QUANTUM, True)],
        ids=["tail", "no-tail", "endurance", "tier", "tier-timeline",
             "timeline"])
    def test_plan_fleet(self, kind, tail, timeline, t_scan, packed):
        """One rule a case: trim a pad tail unless the fleet tracks wear
        or is a tier with a timeline; pack unless it has a tier."""
        from repro.core.ssd.endurance.spec import EnduranceSpec
        from repro.hostcache.model import as_hc_params
        from repro.hostcache.spec import HostCacheSpec
        hc = HostCacheSpec() if kind == "tier" else None
        p = default_params(
            CFG, "ips", 0.0,
            endurance=EnduranceSpec() if kind == "endurance" else None)
        if hc is not None:
            p = p._replace(hostcache=as_hc_params(hc))
        is_write = np.full((2, 2 * TRIM_QUANTUM), -1, np.int32)
        is_write[:, :100 if tail else 2 * TRIM_QUANTUM] = 1
        assert fleet.plan_fleet(
            CFG, N_LOGICAL, is_write, fleet.stack_params([p, p]),
            hostcache=hc, timeline_ops=timeline) == fleet.FleetPlan(
                t_scan, packed)

    def test_trim_len(self):
        is_write = np.full((2, 4 * TRIM_QUANTUM), -1, np.int32)
        is_write[0, : TRIM_QUANTUM + 7] = 1
        is_write[1, : 100] = 0
        assert fleet._trim_len(is_write) == 2 * TRIM_QUANTUM
        # all-pad fleet still scans at least one quantum
        assert fleet._trim_len(np.full((1, 2 * TRIM_QUANTUM), -1,
                                       np.int32)) == TRIM_QUANTUM


class TestFleetSatellites:
    def test_cell_quantum_lcm_contract(self):
        import math
        n_dev = len(jax.devices())
        assert fleet.cell_quantum() == n_dev
        for bucket in (1, 2, 3, 4, 6, 7):
            q = fleet.cell_quantum(bucket)
            assert q == math.lcm(bucket, n_dev)
            assert q % bucket == 0 and q % n_dev == 0

    def test_shard_skipped_counted(self):
        """A non-dividing cell axis falls back unsharded and increments
        the structured `shard_skip_count` counter (surfaced in BENCH run
        metadata + history records) instead of warning to stderr."""
        devices = list(jax.devices()) * 2     # synthetic 2-device mesh
        tree = {"x": jnp.ones((3, 4))}        # 3 cells don't divide 2
        before = fleet.shard_skip_count()
        out = fleet.shard_cells(tree, devices=devices)
        assert out is tree                    # unsharded, data untouched
        assert fleet.shard_skip_count() == before + 1
        # the single-device no-op (nothing to shard) must NOT count
        # (a real dividing multi-device shard can't be exercised on one
        # CPU: a duplicated-device mesh trips jax's reshard internals)
        ok = fleet.shard_cells({"x": jnp.ones((4, 4))},
                               devices=jax.devices()[:1])
        assert fleet.shard_skip_count() == before + 1
        assert ok is not None


class TestCommittedArtifacts:
    def test_step_throughput_schema(self):
        import os
        from repro.sweep.store import check_step_throughput, load_bench
        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_step_throughput.json")
        if not os.path.exists(path):
            pytest.skip("BENCH_step_throughput.json not committed")
        doc = check_step_throughput(load_bench(path), min_speedup=3.0)
        assert doc["geomean_speedup"]["compressed"] >= 5.0, \
            "acceptance floor: >= 5x warm ops/s on the daily MSR sweep"

    def test_paper_geomeans_recompute(self):
        """The committed paper-grid artifact's stored geomeans must be
        reproducible from its own per-cell results (guards the
        summaries the compressed/packed sweep is gated against)."""
        import os
        from repro.sweep.report import geomean, normalize_to_baseline
        from repro.sweep.store import load_bench
        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_sweep_paper.json")
        if not os.path.exists(path):
            pytest.skip("BENCH_sweep_paper.json not committed")
        doc = load_bench(path)
        for metric in ("mean_write_latency_ms", "wa_paper"):
            norm = normalize_to_baseline(doc["results"], metric)
            agg = {}
            for key, ratio in norm.items():
                if "&" in key:
                    continue                  # headline cells only
                trace, mode, policy = key.split("/")
                agg.setdefault(f"{mode}/{policy}", []).append(ratio)
            for gkey, vals in agg.items():
                stored = doc["geomeans"][gkey][metric]
                assert np.isclose(geomean(vals), stored, rtol=1e-9), \
                    (gkey, metric)
