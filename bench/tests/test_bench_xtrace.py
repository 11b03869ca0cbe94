"""The reduction from a profiler trace to busy and idle time, the loop
period and the busiest operations."""
import os
import statistics

import benchtest  # noqa: F401  (import paths)
import pytest

from benchlib import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, s, e):
    return xtrace.Event(plane, line, name, float(s), float(e))


def built():
    return [
        ev(HOST, "main", "bench.iteration", 1000, 5000),
        ev(HOST, "main", "bench.iteration", 6000, 11000),
        ev(DEV, "XLA Modules", "jit__run_fleet_trim(12)", 1500, 3500),
        ev(DEV, "XLA Modules", "jit__run_fleet(34)", 7000, 10000),
        ev(DEV, "XLA Modules", "jit_convert_element_type(5)", 500, 1200),
        ev(DEV, "XLA Ops", "fusion.1", 1500, 2500),
        ev(DEV, "XLA Ops", "fusion.2", 2400, 3500),
        ev(DEV, "XLA Ops", "fusion.1", 7000, 8000),
        ev(DEV, "XLA Ops", "copy.3", 9000, 10000),
        ev(DEV, "XLA Ops", "convert.4", 500, 1200),
    ]


def test_busy_idle_and_programs_from_built_events():
    r = xtrace.reduce(built(), window_mark="bench.iteration")
    assert r.window_s == pytest.approx(10000e-9)
    # ops union inside [1000, 11000]: 1000-1200, 1500-3500, 7000-8000,
    # 9000-10000
    assert r.busy_s == pytest.approx(4200e-9)
    assert r.idle_share == pytest.approx(1 - 0.42)
    assert r.loop_period_s is None          # no operation recurs
    assert r.top_ops[0] == ("fusion.1", pytest.approx(2000e-9))
    longest = [(e - s) for s, e in r.idle_gaps]
    assert longest == sorted(longest, reverse=True)
    assert longest[0] == pytest.approx(3500)        # 3500 - 7000


def test_modules_stand_in_where_there_is_no_op_line():
    evs = [e for e in built() if e.line != "XLA Ops"]
    r = xtrace.reduce(evs, window_mark="bench.iteration")
    # 1000-1200 (clipped convert), 1500-3500, 7000-10000
    assert r.busy_s == pytest.approx(5200e-9)
    assert dict(r.top_ops)["_run_fleet"] == pytest.approx(3000e-9)


def test_without_a_mark_the_device_events_bound_the_window():
    r = xtrace.reduce([e for e in built() if e.plane == DEV])
    assert r.window_s == pytest.approx(9500e-9)     # 500 .. 10000


def test_busy_is_averaged_over_chips():
    evs = built() + [ev("/device:TPU:1", "XLA Ops", "fusion.1", 1000, 11000)]
    r = xtrace.reduce(evs, window_mark="bench.iteration")
    assert r.planes == 2
    assert r.busy_s == pytest.approx((4200e-9 + 10000e-9) / 2)


def loop(trips, period, t0=0.0, inner=3):
    """A scan of `trips` trips: two body operations once per trip, one
    inside an inner loop of `inner` trips, after a ramp operation."""
    evs = [ev(DEV, "XLA Ops", "%copy.9 = s32[11]{0} copy(%p)", t0, t0 + 50)]
    for k in range(trips):
        s = t0 + 100 + k * period
        evs.append(ev(DEV, "XLA Ops", "%fusion.1 = f32[11]{0} fusion(%a)",
                      s, s + period * 0.3))
        evs.append(ev(DEV, "XLA Ops", "%fusion.2 = s32[11]{0} fusion(%b)",
                      s + period * 0.4, s + period * 0.6))
        for j in range(inner):
            u = s + period * (0.7 + 0.1 * j)
            evs.append(ev(DEV, "XLA Ops", "%add.3 = s32[] add(%c, %d)",
                          u, u + period * 0.05))
    return evs


def test_loop_period_is_the_trip_time_of_the_body():
    evs = loop(300, 20000.0)
    r = xtrace.reduce(evs)
    assert r.loop_period_s == pytest.approx(20000e-9)
    # operations are named by their instruction, without the text
    assert {n for n, _ in r.top_ops} == {"%fusion.1", "%fusion.2",
                                         "%add.3", "%copy.9"}
    # a window cut inside the loop counts only whole operations in it
    mark = ev(HOST, "main", "bench.traced", 100 + 50 * 20000.0 + 7,
              100 + 250 * 20000.0 + 7)
    r = xtrace.reduce(evs + [mark], window_mark="bench.traced")
    assert r.loop_period_s == pytest.approx(20000e-9)


def test_loop_period_needs_an_operation_that_recurs():
    assert xtrace.loop_period_ns(loop(50, 20000.0, inner=1)) is None
    assert xtrace.loop_period_ns(loop(120, 20000.0)) == pytest.approx(
        20000.0)


def test_no_device_events_is_an_error():
    with pytest.raises(ValueError):
        xtrace.reduce([ev(HOST, "main", "bench.iteration", 0, 1)])


def test_gaps_are_named_by_the_deepest_open_span():
    gaps = [(3500.0, 7000.0), (100.0, 200.0)]
    spans = [("bench.iteration", 1000, 11000, 0),
             ("sweep.dispatch", 3000, 8000, 1),
             ("trace.build", 4000, 6000, 2)]
    assert xtrace.name_gaps(gaps, spans) == [
        ("trace.build", pytest.approx(3500e-9)),
        ("host", pytest.approx(100e-9))]


def test_union_and_gaps_agree():
    iv = [(0, 2), (1, 3), (5, 6), (10, 20)]
    assert xtrace.union_ns(iv, 0, 12) == 6
    gaps = xtrace.gaps_ns(iv, 0, 12)
    assert gaps == [(3, 5), (6, 10)]
    assert sum(e - s for s, e in gaps) + 6 == 12


RECORDED = os.path.join(DATA, "cpu_three_calls.xplane.pb")


def test_recorded_trace():
    """A trace recorded on the CPU: three calls of one jitted program, each
    inside a `bench.iteration` annotation, 5 ms apart. Its XLA work runs
    on host threads, so here those events stand in for a device plane."""
    import jax
    marks = [e for e in xtrace.events(RECORDED,
                                      keep_host=("bench.iteration",))]
    assert [e.name for e in marks] == ["bench.iteration"] * 3
    assert all(e.end_ns > e.start_ns for e in marks)
    with pytest.raises(ValueError):
        xtrace.reduce(marks, window_mark="bench.iteration")
    pd = jax.profiler.ProfileData.from_file(RECORDED)
    work = [ev(DEV, "XLA Ops", e.name, e.start_ns,
               e.start_ns + e.duration_ns)
            for p in pd.planes for line in p.lines
            if line.name.startswith("tf_XLA")
            for e in line.events if e.duration_ns > 0
            and not e.name.startswith(("end:", "Threadpool"))]
    r = xtrace.reduce(marks + work, window_mark="bench.iteration")
    lo, hi = marks[0].start_ns, marks[-1].end_ns
    # brute force: mark every nanosecond covered by some operation
    covered = set()
    for e in work:
        covered.update(range(int(max(e.start_ns, lo)),
                             int(min(e.end_ns, hi))))
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    assert r.busy_s == pytest.approx(len(covered) / 1e9)
    assert 0 < r.idle_share < 1
    # the 5 ms sleeps between calls are the longest idle gaps
    assert len(r.idle_gaps) >= 2
    assert (r.idle_gaps[0][1] - r.idle_gaps[0][0]) > 4e6


TPU_RECORDED = os.path.join(DATA, "tpu_three_scans.xplane.pb")


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e chip: three runs of one jitted
    300-trip scan, each inside a `bench.traced` annotation, 5 ms apart.
    Each run is one `XLA Modules` event; its `while` holds the body's
    operations on the `XLA Ops` line."""
    evs = xtrace.events(TPU_RECORDED, keep_host=("bench.traced",))
    mods = [e for e in evs if e.line == "XLA Modules"]
    ops = [e for e in evs if e.line == "XLA Ops"]
    assert [xtrace.program_of(e.name) for e in mods] == ["_lambda"] * 3
    assert sum(e.name.startswith("bench.traced") for e in evs) == 3
    whiles = [e for e in ops if xtrace.op_name(e.name) == "%while"]
    assert len(whiles) == 3
    inner = xtrace.innermost(ops)
    assert len(inner) == len(ops) - 3
    assert not any(xtrace.op_name(e.name) == "%while" for e in inner)
    r = xtrace.reduce(evs)
    # busy: the body's operations, not the loops that hold them
    assert r.busy_s == pytest.approx(
        xtrace.union_ns([(e.start_ns, e.end_ns) for e in inner],
                        r.lo_ns, r.hi_ns) / 1e9)
    assert r.busy_s < sum(e.end_ns - e.start_ns for e in whiles) / 1e9
    assert "%while" not in dict(r.top_ops)
    # one trip of the scan: a program run over its 300 trips, not the
    # span of all three runs
    trip = statistics.median((e.end_ns - e.start_ns) / 300 for e in mods)
    assert r.loop_period_s * 1e9 == pytest.approx(trip, rel=0.02)
