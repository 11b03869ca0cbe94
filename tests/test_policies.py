"""Policy-engine tests (DESIGN.md §8).

The load-bearing contract: the four paper policies, assembled from
mechanism layers by `policies.engine`, are BIT-IDENTICAL — latencies,
counters, final state — to the pre-refactor monolithic scan vendored in
tests/golden_sim.py, in both closed-loop (bursty) and replay (daily)
modes. Everything else rides along: registry/axis validation, the
every-registered-policy-runs-end-to-end property on the quick grid's
workloads, beyond-paper composition behavior, declared-baseline
normalization, and runner group timings.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from golden_sim import golden_run_trace
from repro.configs.ssd_paper import PAPER_SSD
from repro.core.ssd.driver import _agc_waste_p
from repro.core.ssd.policies import (PAPER_POLICIES, PolicySpec,
                                     get_entry, get_spec, policy_names,
                                     register, resolve_spec,
                                     state_fields_used, tracked_region,
                                     validate_spec)
from repro.core.ssd.policies.engine import lane_add, lane_get, lane_set
from repro.core.ssd.sim import (CTR, SimState, default_params, flush_cache,
                                run_trace, summarize)
from repro.core.ssd.workloads import make_trace, truncate_trace
from repro.sweep.grid import SweepPoint, named_grid
from repro.sweep.report import normalize_points

CFG = PAPER_SSD.scaled(128)
N_LOGICAL = min(CFG.total_pages, 1 << 16)
MAX_OPS = 4096          # truncated traces: full-scan equivalence is implied
#                         because the scan step has no length dependence


def _hm0(mode):
    return truncate_trace(
        make_trace("hm_0", N_LOGICAL, mode=mode,
                   capacity_pages=CFG.total_pages), MAX_OPS)


def _rand_trace(seed=7, n=2048):
    rng = np.random.default_rng(seed)
    return {
        "arrival_ms": np.cumsum(rng.exponential(1.0, n)).astype(np.float32),
        "lba": rng.integers(0, 4096, n).astype(np.int32),
        "is_write": rng.choice(np.array([0, 1], np.int8), n, p=[0.3, 0.7]),
    }


def _assert_same_run(lat_a, st_a, lat_b, st_b, tag):
    assert np.array_equal(np.asarray(lat_a), np.asarray(lat_b)), \
        f"latency mismatch [{tag}]"
    for f in SimState._fields:
        assert np.array_equal(np.asarray(getattr(st_a, f)),
                              np.asarray(getattr(st_b, f))), \
            f"state.{f} mismatch [{tag}]"


class TestGoldenBitIdentity:
    """Paper policies through the engine == the vendored seed monolith."""

    @pytest.mark.parametrize("mode", ["bursty", "daily"])
    @pytest.mark.parametrize("policy", PAPER_POLICIES)
    def test_hm0(self, policy, mode):
        trace = _hm0(mode)
        waste = _agc_waste_p("hm_0")
        closed = mode == "bursty"
        lat_g, st_g = golden_run_trace(CFG, policy, trace,
                                       closed_loop=closed,
                                       n_logical=N_LOGICAL, waste_p=waste)
        lat_n, st_n = run_trace(CFG, policy, trace, closed_loop=closed,
                                n_logical=N_LOGICAL, waste_p=waste)
        # golden state is a different NamedTuple type with the same fields
        _assert_same_run(lat_g, SimState(*st_g), lat_n, st_n,
                         f"{policy}/{mode}/hm_0")

    @pytest.mark.parametrize("policy", PAPER_POLICIES)
    def test_random_trace_replay(self, policy):
        trace = _rand_trace()
        lat_g, st_g = golden_run_trace(CFG, policy, trace,
                                       closed_loop=False, n_logical=4096,
                                       waste_p=0.1)
        lat_n, st_n = run_trace(CFG, policy, trace, closed_loop=False,
                                n_logical=4096, waste_p=0.1)
        _assert_same_run(lat_g, SimState(*st_g), lat_n, st_n,
                         f"{policy}/random")


class TestSpecAndRegistry:
    def test_paper_policies_registered(self):
        assert set(PAPER_POLICIES) <= set(policy_names())
        assert {"dyn_slc", "ips_lazy"} <= set(policy_names())

    def test_compositions_of_paper_policies(self):
        assert get_spec("baseline") == PolicySpec("static", "watermark",
                                                  "migrate", "greedy")
        assert get_spec("ips") == PolicySpec("static", "exhaustion",
                                             "reprogram", "none")
        assert get_spec("ips_agc") == PolicySpec("static", "exhaustion",
                                                 "reprogram", "agc")
        assert get_spec("coop") == PolicySpec("dual", "exhaustion",
                                              "reprogram", "agc")

    def test_unknown_and_duplicate(self):
        with pytest.raises(ValueError, match="unknown policy"):
            get_spec("nope")
        with pytest.raises(ValueError, match="already registered"):
            register("baseline", get_spec("baseline"))

    def test_register_rejects_unregistered_baseline(self):
        with pytest.raises(ValueError, match="not registered"):
            register("typo_policy", get_spec("ips"), baseline="basline")
        assert "typo_policy" not in policy_names()

    @pytest.mark.parametrize("spec", [
        PolicySpec("static", "watermark", "migrate", "agc"),    # agc w/o rp
        PolicySpec("dual", "watermark", "migrate", "greedy"),   # dual+migrate
        PolicySpec("static", "watermark", "reprogram", "none"),  # rp trigger
        PolicySpec("static", "exhaustion", "migrate", "none"),  # mig trigger
        PolicySpec("static", "watermark", "migrate", "none"),   # dead trigger
        PolicySpec("adaptive", "exhaustion", "reprogram", "none"),
        PolicySpec("static", "exhaustion", "reprogram", "greedy"),  # dead
        PolicySpec("bogus", "watermark", "migrate", "greedy"),  # bad axis
    ])
    def test_invalid_compositions_rejected(self, spec):
        with pytest.raises(ValueError):
            validate_spec(spec)

    def test_state_fields_declared(self):
        for name in policy_names():
            used = state_fields_used(get_spec(name))
            assert used <= set(SimState._fields), name

    def test_tracked_region_matches_flush_semantics(self):
        assert tracked_region(get_spec("baseline")) == "basic"
        assert tracked_region(get_spec("dyn_slc")) == "basic"
        assert tracked_region(get_spec("coop")) == "trad"
        assert tracked_region(get_spec("ips_lazy")) == "trad"
        assert tracked_region(get_spec("ips")) is None
        assert tracked_region(get_spec("ips_agc")) is None

    def test_declared_baselines(self):
        assert get_entry("dyn_slc").baseline == "baseline"
        assert get_entry("ips_lazy").baseline == "coop"

    def test_resolve_spec_accepts_raw_spec(self):
        spec = PolicySpec("static", "idle_gap", "migrate", "greedy")
        assert resolve_spec(spec) is spec
        with pytest.raises(ValueError):
            resolve_spec(PolicySpec("static", "exhaustion", "migrate",
                                    "none"))


class TestEveryPolicyEndToEnd:
    """Registry property: every registered policy runs through the sweep
    runner on the quick grid's workload cells and produces sane metrics."""

    def test_quick_grid_all_policies(self):
        from repro.sweep.runner import run_sweep
        coords = {(pt.trace, pt.mode) for pt in named_grid("quick")}
        points = [SweepPoint(trace=t, mode=m, policy=p,
                             baseline=get_entry(p).baseline)
                  for (t, m) in sorted(coords)
                  for p in policy_names()]
        timings = []
        res = run_sweep(CFG, points, max_ops=2048, timings=timings)
        assert set(res) == set(points)
        for pt, out in res.items():
            assert np.isfinite(out["mean_write_latency_ms"]), pt
            assert out["mean_write_latency_ms"] > 0, pt
            assert out["wa_paper"] >= 1.0 - 1e-6, pt
            assert 0 < out["n_ops"] <= 2048
        # group timing metadata covers every (composition, mode) group
        specs = {(get_spec(pt.policy), pt.mode) for pt in points}
        assert len(timings) == len(specs)
        for g in timings:
            assert g["dispatch_s"] >= 0 and g["block_s"] >= 0
            assert "+" in g["composition"]
            assert g["devices"] == len(jax.devices())


class TestBeyondPaperBehavior:
    def test_ips_lazy_equals_coop_closed_loop(self):
        """No idle in the bursty mode => the compositions coincide there;
        composing the idle axis away must not perturb anything else."""
        trace = _hm0("bursty")
        lat_c, st_c = run_trace(CFG, "coop", trace, closed_loop=True,
                                n_logical=N_LOGICAL)
        lat_l, st_l = run_trace(CFG, "ips_lazy", trace, closed_loop=True,
                                n_logical=N_LOGICAL)
        _assert_same_run(lat_c, st_c, lat_l, st_l, "coop vs ips_lazy")

    def test_ips_lazy_does_no_idle_work(self):
        trace = _hm0("daily")
        _, st_c = run_trace(CFG, "coop", trace, closed_loop=False,
                            n_logical=N_LOGICAL, waste_p=0.1)
        _, st_l = run_trace(CFG, "ips_lazy", trace, closed_loop=False,
                            n_logical=N_LOGICAL, waste_p=0.1)
        c_c, c_l = np.asarray(st_c.counters), np.asarray(st_l.counters)
        assert c_l[CTR["rp_agc"]] == 0 and c_l[CTR["rp_trad"]] == 0
        assert c_l[CTR["mig_w"]] == 0       # nothing migrates before flush
        # the reference composition does reclaim during idle on this trace
        assert c_c[CTR["rp_trad"]] + c_c[CTR["rp_agc"]] > 0

    def test_ips_lazy_flushes_traditional_region(self):
        trace = _hm0("daily")
        _, st = run_trace(CFG, "ips_lazy", trace, closed_loop=False,
                          n_logical=N_LOGICAL)
        flushed = flush_cache(CFG, st, "ips_lazy")
        before = float(st.counters[CTR["mig_w"]])
        after = float(flushed.counters[CTR["mig_w"]])
        assert after - before == float(np.asarray(st.valid_mig).sum())

    def test_dyn_slc_absorbs_more_bursty_writes(self):
        """Adaptive sizing: crossing the watermark unlocks cap_boost extra
        SLC pages, moving the Fig. 3 cliff past the static capacity."""
        cache_pages = CFG.slc_cap_pages * CFG.num_planes
        n = 3 * cache_pages
        trace = {"arrival_ms": np.zeros(n, np.float32),
                 "lba": (np.arange(n) % 60000).astype(np.int32),
                 "is_write": np.ones(n, np.int8)}
        fracs = {}
        for policy in ("baseline", "dyn_slc"):
            lat, _ = run_trace(CFG, policy, trace, closed_loop=True,
                               n_logical=60000)
            fracs[policy] = float(
                (np.asarray(lat) == CFG.timing.slc_write_ms).mean())
        # default cap_boost == cap_basic: twice the SLC-speed volume
        assert fracs["dyn_slc"] >= 1.9 * fracs["baseline"]

    def test_dyn_slc_with_zero_boost_is_baseline(self):
        """cap_boost is traced: zeroing it recovers baseline bit-for-bit
        (the adaptive allocation degenerates to static)."""
        trace = _hm0("daily")
        params = default_params(CFG, "dyn_slc")._replace(
            cap_boost=jnp.int32(0))
        lat_d, st_d = run_trace(CFG, "dyn_slc", trace, closed_loop=False,
                                n_logical=N_LOGICAL, params=params)
        lat_b, st_b = run_trace(CFG, "baseline", trace, closed_loop=False,
                                n_logical=N_LOGICAL)
        _assert_same_run(lat_d, st_d, lat_b, st_b, "dyn_slc boost=0")

    def test_default_params_per_composition(self):
        p = default_params(CFG, "ips_lazy")
        assert int(p.cap_basic) == CFG.coop_ips_pages
        assert int(p.cap_trad) == CFG.coop_trad_pages
        d = default_params(CFG, "dyn_slc")
        assert int(d.cap_basic) == CFG.slc_cap_pages
        assert int(d.cap_boost) == CFG.slc_cap_pages
        assert int(default_params(CFG, "baseline").cap_boost) == 0


class TestDeclaredBaselineNormalization:
    def test_beyond_grid_pairs_ips_lazy_with_coop(self):
        pts = named_grid("beyond")
        lazy = [p for p in pts if p.policy == "ips_lazy"]
        assert lazy and all(p.baseline == "coop" for p in lazy)
        # synthetic results: ips_lazy 3.0 vs coop 2.0 -> ratio 1.5
        res = {}
        for p in pts:
            val = {"ips_lazy": 3.0, "coop": 2.0,
                   "dyn_slc": 1.0, "baseline": 4.0}[p.policy]
            res[p] = {"m": val}
        norm = normalize_points(res, "m")
        for p in lazy:
            assert norm[p] == pytest.approx(1.5)
        for p in pts:
            if p.policy == "dyn_slc":
                assert norm[p] == pytest.approx(0.25)   # vs baseline
            if p.policy in ("baseline", "coop"):
                assert p not in norm                    # reference cells

    def test_baseline_field_not_identity(self):
        a = SweepPoint("hm_0", "daily", "coop")
        b = SweepPoint("hm_0", "daily", "coop", baseline="coop")
        assert a == b and hash(a) == hash(b) and a.key == b.key


class TestSummaryThroughEngine:
    def test_summarize_consistent_for_new_policies(self):
        trace = _rand_trace(seed=3, n=1024)
        for policy in ("dyn_slc", "ips_lazy"):
            lat, st = run_trace(CFG, policy, trace, closed_loop=False,
                                n_logical=4096)
            c = np.asarray(st.counters)
            # every host page lands somewhere, exactly once
            assert (c[CTR["slc_w"]] + c[CTR["tlc_w"]] + c[CTR["rp_host"]]
                    == pytest.approx(c[CTR["host_w"]]))
            summ = summarize(jnp.asarray(lat),
                             {"is_write": jnp.asarray(trace["is_write"])},
                             st)
            assert float(summ["wa_paper"]) >= 1.0 - 1e-6


class TestMaskedLaneOps:
    """The step core reads and writes plane-indexed state through masked
    lane ops (engine.lane_get/lane_set/lane_add) instead of `x[i]` /
    `x.at[i]`. They must be the indexing ops bit for bit, vmapped over a
    fleet's cells and not, for every carry dtype: -0.0, infinities and
    NaN payloads read back unchanged, narrow integers wrap alike, and the
    valid_mig sequence (set at the plane, add at the old plane, add at
    the plane) agrees where the two planes coincide."""
    CELLS, P = 11, 128

    def _case(self, dtype, trailing, seed=5):
        rng = np.random.default_rng(seed)
        shape = (self.CELLS, self.P) + trailing
        if np.issubdtype(dtype, np.floating):
            x = rng.normal(0.0, 1e3, shape).astype(dtype)
            v = rng.normal(0.0, 1e3, (self.CELLS,) + trailing).astype(dtype)
            specials = np.array([-0.0, np.inf, -np.inf, np.nan, 0.0],
                                dtype)
            v.reshape(self.CELLS, -1)[:len(specials), 0] = specials
            d = rng.normal(0.0, 1.0, (self.CELLS,) + trailing).astype(dtype)
            d.reshape(self.CELLS, -1)[0, 0] = np.float32(-0.0)
        else:
            info = np.iinfo(dtype)
            x = rng.integers(info.min, info.max, shape,
                             endpoint=True).astype(dtype)
            v = rng.integers(info.min, info.max, (self.CELLS,) + trailing,
                             endpoint=True).astype(dtype)
            d = rng.integers(-3, 4, (self.CELLS,) + trailing).astype(dtype)
            v.reshape(self.CELLS, -1)[:2, 0] = [info.max, info.min]
        plane = rng.integers(0, self.P, self.CELLS).astype(np.int32)
        old = rng.integers(0, self.P, self.CELLS).astype(np.int32)
        old[::3] = plane[::3]                  # old plane == plane
        # the specials also sit in the carry, at the planes read back
        flat = x.reshape(self.CELLS, self.P, -1)
        flat[np.arange(self.CELLS), plane, 0] = v.reshape(self.CELLS, -1)[:, 0]
        return x, v, d, plane, old

    @staticmethod
    def _masked(x, v, d, plane, old):
        lanes = jnp.arange(x.shape[0], dtype=jnp.int32)
        at_p, at_old = lanes == plane, lanes == old
        seq = lane_add(lane_add(lane_set(x, at_p, v), at_old, -d), at_p, d)
        return lane_get(x, at_p), lane_set(x, at_p, v), seq

    @staticmethod
    def _indexed(x, v, d, plane, old):
        seq = x.at[plane].set(v).at[old].add(-d).at[plane].add(d)
        return x[plane], x.at[plane].set(v), seq

    @staticmethod
    def _same_bits(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return a.dtype == b.dtype and a.shape == b.shape and \
            a.tobytes() == b.tobytes()

    @pytest.mark.parametrize("batched", [False, True], ids=["cell", "vmap"])
    @pytest.mark.parametrize("trailing", [(), (8,)], ids=["row", "rows"])
    @pytest.mark.parametrize("dtype", [np.int16, np.int32, np.float32])
    def test_matches_indexing_bit_for_bit(self, dtype, trailing, batched):
        x, v, d, plane, old = self._case(dtype, trailing)
        if batched:
            got = jax.jit(jax.vmap(self._masked))(x, v, d, plane, old)
            want = jax.jit(jax.vmap(self._indexed))(x, v, d, plane, old)
        else:
            masked, indexed = jax.jit(self._masked), jax.jit(self._indexed)
            per_cell = [(masked(*a), indexed(*a))
                        for a in zip(x, v, d, plane, old)]
            got = [np.stack([np.asarray(g[k]) for g, _ in per_cell])
                   for k in range(3)]
            want = [np.stack([np.asarray(w[k]) for _, w in per_cell])
                    for k in range(3)]
        for name, g, w in zip(("get", "set", "set/add/add"), got, want):
            assert self._same_bits(g, w), name
        # the specials really were read: -0.0 keeps its sign bit
        if dtype == np.float32:
            read = np.asarray(got[0]).reshape(self.CELLS, -1)[:, 0]
            assert np.signbit(read[0]) and read[0] == 0.0
            assert np.isinf(read[1]) and np.isnan(read[3])
