"""The per-layer readers of the program's whole-window spans: device idle
share, build time the device does not cover, step time of every trimmed
fleet and jaxpr traces per iteration, on hand-built spans with known
answers."""
import benchtest  # noqa: F401  (import paths)
import pytest

from benchlib import catalog, cell, window

READERS = ("device_idle_share.window", "device_idle_share.build",
           "fleet_step_us.window", "jaxpr_traces_per_iter")


def read(name, spans, seconds=10.0, iterations=2):
    its = [window.Iteration(i, 0.0, 0.0, [], {}, [])
           for i in range(iterations)]
    run = cell.Run(cell=None, window=window.Window(0.0, seconds, its),
                   spans=spans, device=None)
    return catalog.metric_reader(catalog.BENCH_DIR, name)(run)


def sp(name, cat, t0, dur, parent=None, depth=0, **args):
    return {"name": name, "cat": cat, "t0_s": t0, "dur_s": dur,
            "depth": depth, "parent": parent, "args": args}


def scan(t0, dur, t_scan, t_len=8192):
    return sp("device.scan", "device", t0, dur, depth=-1, t_scan=t_scan,
              t_len=t_len)


def tail(t0, dur):
    return sp("device.tail", "device", t0, dur, depth=-1)


def iteration_spans():
    """One 10 s window: a sweep whose trace build (0.0-1.5 s) runs before
    two fleets keep the device busy 2.0-5.0 s and 5.0-9.5 s; a nested
    workload span and a build span inside a device span."""
    return [
        sp("sweep.run", "sweep", 0.0, 10.0),                        # 0
        sp("sweep.group", "sweep", 0.0, 1.5, parent=0, depth=1,
           jaxpr_traces=0, backend_compiles=0),                     # 1
        sp("trace.build", "workload", 0.0, 1.0, parent=1, depth=2),  # 2
        sp("trace.parse", "workload", 0.2, 0.5, parent=2, depth=3),  # 3
        sp("trace.build", "workload", 1.0, 0.5, parent=1, depth=2),  # 4
        sp("sweep.dispatch", "sweep", 1.5, 1.0, parent=0, depth=1,
           jaxpr_traces=60, backend_compiles=0),                    # 5
        sp("trace.build", "workload", 4.5, 1.0, parent=0, depth=1),  # 6
        sp("sweep.dispatch", "sweep", 2.5, 1.0, parent=0, depth=1,
           jaxpr_traces=40, backend_compiles=0),                    # 7
        sp("sweep.block", "sweep", 3.5, 6.0, parent=0, depth=1,
           jaxpr_traces=0, backend_compiles=0),                     # 8
        scan(2.0, 2.5, t_scan=1000),
        tail(4.5, 0.5),
        scan(5.0, 4.0, t_scan=3000),
        tail(9.0, 0.5),
    ]


def test_window_idle_share_is_the_time_no_device_span_covers():
    # busy 2.5 + 0.5 + 4.0 + 0.5 = 7.5 of 10 s
    assert read("device_idle_share.window", iteration_spans()) == \
        pytest.approx(25.0)


def test_build_idle_counts_outermost_builds_outside_device_spans():
    # builds 0.0-1.0 and 1.0-1.5 (the parse inside counted once) lie
    # before any device span; 4.5-5.5 lies inside the device spans
    assert read("device_idle_share.build", iteration_spans()) == \
        pytest.approx(100.0 * 1.5 / 10.0)


def test_build_partly_under_a_device_span_counts_its_uncovered_part():
    spans = [sp("trace.build", "workload", 1.0, 2.0), scan(2.5, 1.0, 8)]
    # 1.0-3.0 built, 2.5-3.5 busy: 1.5 s uncovered of 4 s
    assert read("device_idle_share.build", spans, seconds=4.0) == \
        pytest.approx(100.0 * 1.5 / 4.0)


def test_window_step_time_over_every_trimmed_fleet():
    # (2.5 + 4.0) s over (1000 + 3000) scanned steps
    assert read("fleet_step_us.window", iteration_spans()) == \
        pytest.approx(1e6 * 6.5 / 4000)


def test_window_step_time_leaves_out_full_length_fleets():
    spans = [scan(0.0, 2.0, t_scan=1000), scan(2.0, 9.0, 8192, 8192)]
    assert read("fleet_step_us.window", spans) == pytest.approx(2000.0)
    assert read("fleet_step_us.window", spans[1:]) is None


def test_traces_per_iteration_add_the_phases_counts():
    # 0 + 60 + 40 + 0 traces over two iterations
    assert read("jaxpr_traces_per_iter", iteration_spans()) == \
        pytest.approx(50.0)


def test_zero_idle_zero_build_idle_zero_traces_read_zero():
    spans = [sp("sweep.group", "sweep", 0.0, 4.0, jaxpr_traces=0,
                backend_compiles=0),
             sp("trace.build", "workload", 1.0, 1.0, parent=0, depth=1),
             scan(0.0, 3.0, t_scan=100), tail(3.0, 1.0)]
    for name in ("device_idle_share.window", "device_idle_share.build",
                 "jaxpr_traces_per_iter"):
        value = read(name, spans, seconds=4.0)
        assert value == 0.0 and value is not None, name


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_spans_of_a_program_without_them(name):
    """The spans a program without device spans or counters records."""
    spans = [sp("sweep.dispatch", "sweep", 0.0, 1.0, compiles=0),
             sp("trace.build", "workload", 1.0, 1.0),
             sp("sweep.block", "sweep", 2.0, 2.0)]
    assert read(name, spans) is None
