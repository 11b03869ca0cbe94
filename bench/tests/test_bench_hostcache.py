"""A configuration with a host tier: its points carry the tier, its
traced window reads the per-layer metrics that apply to every cell, and
a reference that leaves the tier out cannot pass `correct`."""
import copy
import os
import time

import benchtest
import pytest

from benchlib import catalog, cell, check, window

SEED = 2 ** 31 + 29
TIER = {"mode": "wb", "flush": "watermark"}


def fixture_traffic() -> dict:
    return catalog.load_json(os.path.join(
        benchtest.BENCH, "tests", "data", "flush-burst-daily.json"))


def host_tier_cell(max_ops=2048, policies=("baseline", "ips_agc")):
    """The paper's drive with a write-back host tier under the program's
    flush_burst scenario, at a size a CPU test holds."""
    c = benchtest.small_cell("paper-msr.daily")
    c.config = dict(copy.deepcopy(c.config), name="hostcache-test",
                    point={"hostcache": dict(TIER)})
    c.traffic = fixture_traffic() | {"max_ops": max_ops,
                                     "policies": list(policies)}
    return c


@pytest.fixture(scope="module")
def host_tier_window():
    """One iteration of the timed path with the program's span tracer
    on, as a window: the cell, the window, its spans."""
    c = host_tier_cell()
    prog = cell.Program(c)
    tracer = prog.Tracer()
    with tracer.activate():
        win = window.run(
            lambda i, pts: prog.sweep(pts),
            lambda i: prog.points(c.traffic,
                                  cell.iteration_seeds(c.traffic, SEED, i)),
            seconds=0.0)
    return c, win, tracer.spans


def test_points_carry_the_host_tier():
    from repro.hostcache.spec import HostCacheSpec
    c = host_tier_cell()
    pts = cell.Program(c).points(c.traffic, [1, 2])
    assert len(pts) == 4
    assert {p.hostcache for p in pts} == {HostCacheSpec(**TIER)}


def test_points_without_a_host_tier_stay_as_they_were():
    c = benchtest.small_cell("paper-msr.daily")
    assert "point" not in c.config
    pts = cell.Program(c).points(c.traffic, [1])
    assert {p.hostcache for p in pts} == {None}


@pytest.mark.parametrize("group,match", [
    ({"hostcache": {"mode": "wb", "watermark_hi": 0.9}}, "watermark_hi"),
    ({"hostcache": {"mode": "wb", "sets": 128, "lines": 1024}}, "lines"),
    ({"host_cache": {"mode": "wb"}}, "host_cache"),
    ({"repeat": {"n": 2}}, "repeat"),
    ({"cache_fraction": 0.5}, "cache_fraction"),
    ({"seed": 3}, "seed")])
def test_an_unknown_host_tier_field_is_an_error(group, match):
    c = host_tier_cell()
    c.config["point"] = group
    with pytest.raises(TypeError, match=match):
        cell.Program(c)


def test_a_point_group_sets_any_sweep_point_field():
    from repro.core.ssd.endurance.spec import EnduranceSpec
    c = host_tier_cell()
    c.config["point"] = {"repeat": 2, "cache_frac": 0.5,
                         "endurance": {"w_rp": 1.5}}
    pts = cell.Program(c).points(c.traffic, [1])
    assert {(p.repeat, p.cache_frac, p.endurance, p.hostcache)
            for p in pts} == {(2, 0.5, EnduranceSpec(w_rp=1.5), None)}


def test_a_host_tier_fleet_scans_untrimmed(host_tier_window):
    _, win, _ = host_tier_window
    timings = [g for it in win.iterations for g in it.timings]
    assert timings
    assert all(g["t_scan"] == g["t_len"] for g in timings)


@pytest.mark.parametrize("name", ["trace_build_share", "lane_useful_share",
                                  "device_idle_share.window",
                                  "device_idle_share.build",
                                  "jaxpr_traces_per_iter"])
def test_every_cell_metric_reads_a_host_tier_window(name, host_tier_window):
    c, win, spans = host_tier_window
    value = catalog.metric_reader(c.bench_dir, name)(
        cell.Run(c, win, spans, None))
    assert isinstance(value, float)
    assert value == value


def test_the_trimmed_step_reads_nothing_in_a_host_tier_window(
        host_tier_window):
    c, win, spans = host_tier_window
    read = catalog.metric_reader(c.bench_dir, "fleet_step_us.window")
    assert read(cell.Run(c, win, spans, None)) is None


def test_a_reference_that_leaves_out_the_tier_fails(host_tier_window):
    c, win, _ = host_tier_window
    pts = win.iterations[0].points
    got = [win.iterations[0].results[p] for p in pts]
    host_keys = {k for k in got[0] if k.startswith("host_")}
    assert {"host_absorbed", "host_dev_ops", "host_flush_w"} <= host_keys
    without = [{k: v for k, v in g.items() if not k.startswith("host_")}
               for g in got]
    cmp = check.compare(got, without, [p.key for p in pts])
    assert cmp.counter_mismatch == len(host_keys) * len(pts)
    assert cmp.float_rel_gap == 0.0
    readings = {"cells_missing": 0, "counter_mismatch":
                cmp.counter_mismatch, "float_rel_gap": cmp.float_rel_gap}
    assert not check.verdict(readings, c.traffic["limits"])
    # the same answers with the tier in them pass
    same = check.compare(got, [dict(g) for g in got], [p.key for p in pts])
    assert same.counter_mismatch == 0


STUB = '''from types import SimpleNamespace

from benchlib import cell


def results(config, traffic, points, ftype, device):
    """The program's own answers{what}."""
    prog = cell.Program(SimpleNamespace(config=config, traffic=traffic))
    res, _ = prog.sweep(points)
    return [{{k: v for k, v in res[p].items() if {keep}}} for p in points]
'''


@pytest.mark.parametrize("what,keep,correct", [
    ("", "True", True),
    (", the host tier's left out", "not k.startswith('host_')", False)])
def test_a_run_compares_with_the_reference_the_configuration_names(
        tmp_path, what, keep, correct):
    """A whole run of a host-tier cell, its reference a stub named by the
    configuration; the stub echoes the program, with or without the
    tier's numbers."""
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "echo.py").write_text(
        STUB.format(what=what, keep=keep))
    c = host_tier_cell(max_ops=1024, policies=("ips",))
    c.config["reference"] = "echo"
    c.bench_dir = str(tmp_path)
    out = cell.run_cell(c, seed=SEED, seconds=0.01, trace=False,
                        t_start=time.perf_counter(),
                        device={"platform": "cpu", "kind": "cpu",
                                "count": 1})
    assert out["correct"] is correct
    assert (out["checks"]["counter_mismatch"]["value"] > 0) is not correct
