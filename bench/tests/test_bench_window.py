"""The window counts whole iterations, and its rate credits live trace
ops of real cells only (no pad ops, no pad cells)."""
import benchtest  # noqa: F401  (import paths)
import pytest

from benchlib import catalog, cell, window, xtrace


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def fake_run(durations, n_ops, seconds, drop=()):
    """Iterations of the given lengths; each returns results for its
    points (two real cells with `n_ops` live ops of 8 padded ones) and the
    runner's timings of one fleet padded with one pad cell."""
    clock = FakeClock()

    def iterate(i, pts):
        clock.t += durations[i]
        res = {p: {"n_ops": n_ops[j]} for j, p in enumerate(pts)
               if p not in drop}
        return res, [{"cells": 2, "pad": 1, "t_scan": 6, "t_len": 8}]

    return window.run(iterate, lambda i: [f"a{i}", f"b{i}"], seconds,
                      clock=clock)


def test_whole_iterations_until_one_ends_past_the_limit():
    win = fake_run([2.0, 2.0, 2.0, 2.0], [3, 5], seconds=5.0)
    # 2, 4, 6: the third iteration is the first to end at or after 5 s
    assert len(win.iterations) == 3
    assert win.seconds == pytest.approx(6.0)
    assert win.live_ops == 3 * (3 + 5)
    assert win.rate == pytest.approx(24 / 6.0)
    assert win.attempted == 6 and win.missing == 0


def test_one_long_iteration_is_the_whole_window():
    win = fake_run([7.5], [10, 20], seconds=1.0)
    assert len(win.iterations) == 1
    assert win.rate == pytest.approx(30 / 7.5)


def test_missing_cells_are_counted_not_credited():
    win = fake_run([3.0, 3.0], [4, 4], seconds=5.0, drop={"b0"})
    assert win.missing == 1
    assert win.live_ops == 4 + 8


def test_lane_useful_share_counts_pad_cells_and_scanned_steps():
    win = fake_run([2.0, 2.0], [3, 5], seconds=3.0)
    read = catalog.metric_reader(catalog.BENCH_DIR, "lane_useful_share")
    run = cell.Run(cell=None, window=win, spans=[], device=None)
    # live 8 per iteration over (2 cells + 1 pad) x 6 scanned steps
    assert read(run) == pytest.approx(100.0 * 16 / 36)


def test_trace_build_share_counts_outermost_workload_spans():
    win = fake_run([4.0], [1, 1], seconds=1.0)
    spans = [{"cat": "workload", "dur_s": 1.0, "parent": None},
             {"cat": "workload", "dur_s": 0.5, "parent": 0},
             {"cat": "sweep", "dur_s": 2.0, "parent": None},
             {"cat": "workload", "dur_s": 0.25, "parent": 2}]
    read = catalog.metric_reader(catalog.BENCH_DIR, "trace_build_share")
    run = cell.Run(cell=None, window=win, spans=spans, device=None)
    assert read(run) == pytest.approx(100.0 * 1.25 / 4.0)


def test_device_readers_find_nothing_without_a_trace():
    win = fake_run([4.0], [1, 1], seconds=1.0)
    run = cell.Run(cell=None, window=win, spans=[], device=None)
    for name in ("fleet_step_us.trim", "device_idle_share"):
        assert catalog.metric_reader(catalog.BENCH_DIR, name)(run) is None


def test_iteration_split_puts_each_span_in_the_iteration_it_starts_in():
    win = fake_run([2.0, 3.0], [5, 6], seconds=4.0)     # 100-102, 102-105
    t0 = 99.0                    # the tracer's clock starts 1 s earlier
    spans = [{"name": n, "t0_s": a, "dur_s": d} for n, a, d in [
        ("sweep.group", 1.0, 0.5), ("device.scan", 1.5, 0.25),
        ("device.scan", 2.0, 0.5), ("device.tail", 2.9, 0.25),
        ("sweep.group", 3.0, 1.0), ("device.scan", 4.0, 1.5),
        ("workload", 3.5, 0.5)]]
    lines = cell.iteration_split(win, spans, t0)
    assert lines == [
        "iteration 0: 2.000 s; sweep.group 0.500 sweep.dispatch 0.000 "
        "sweep.block 0.000 device.scan 0.750 device.tail 0.250; "
        "fleet scans 0.250 0.500",
        "iteration 1: 3.000 s; sweep.group 1.000 sweep.dispatch 0.000 "
        "sweep.block 0.000 device.scan 1.500 device.tail 0.000; "
        "fleet scans 1.500"]


def test_iteration_seeds():
    assert cell.iteration_seeds({}, 10, 2) == [12]
    assert cell.iteration_seeds({"seeds_per_iteration": 8}, 10, 1) == \
        list(range(18, 26))


def _sliced_run(period_s, t_scan):
    """A traced run whose slice lay in a fleet that scanned `t_scan` of
    its 8 padded steps, the device looping at `period_s` per trip."""
    win = fake_run([2.0], [3, 5], seconds=1.0)
    red = xtrace.Reduction(
        window_s=1.0, busy_s=0.9, planes=1, loop_period_s=period_s,
        top_ops=[], idle_gaps=[], lo_ns=0.0, hi_ns=1e9)
    group = {"cells": 2, "pad": 1, "t_scan": t_scan, "t_len": 8}
    return cell.Run(cell=None, window=win, spans=[], device=red,
                    traced_group=group)


@pytest.mark.parametrize("t_scan", [6, 8])
def test_trim_step_time_reads_the_traced_fleet_loop(t_scan):
    read = catalog.metric_reader(catalog.BENCH_DIR, "fleet_step_us.trim")
    if t_scan < 8:          # the trimmed program ran: its step time
        assert read(_sliced_run(20e-6, t_scan)) == pytest.approx(20.0)
    else:                   # the full-length program ran: nothing
        assert read(_sliced_run(20e-6, t_scan)) is None
    # no loop found in the slice: nothing
    assert read(_sliced_run(None, t_scan)) is None
