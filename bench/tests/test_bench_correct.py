"""`correct` comes out false when the timed path is broken underneath,
and for the control; true for the program as it stands. Everything but
the look for a chip runs, at a size a CPU test holds.

Faults, one per kind a sweep cell can have on one chip (the exchange
between chips does not exist there):
  * the step returns its state unchanged: every cell reports the initial
    state's summary;
  * half of the batch left out: half the cells return no result, or
    return a copy of another cell's;
  * an answer altered where it is produced: one counter of one cell.
The control puts the reference computed in bfloat16 in the program's
place.
"""
import time

import benchtest
import pytest

from benchlib import cell

SEED = 2 ** 31 + 3


class Broken(cell.Program):
    """The program with its results corrupted by `fault` on the way out
    of the timed path."""

    def __init__(self, c, fault):
        super().__init__(c)
        self.fault = fault
        self.cell = c

    def sweep(self, points):
        res, timings = super().sweep(points)
        return self.fault(self, points, res), timings


def unchanged_state(prog, points, res):
    out = {}
    for p, r in res.items():
        zero = {k: 0.0 for k in r}
        zero.update(wa_paper=1.0, wa_raw=1.0, n_ops=r["n_ops"])
        out[p] = zero
    return out


def half_left_out(prog, points, res):
    return {p: res[p] for p in points[::2]}


def half_copied(prog, points, res):
    out = dict(res)
    for a, b in zip(points[::2], points[1::2]):
        out[b] = dict(res[a], n_ops=res[b]["n_ops"])
    return out


def one_counter_altered(prog, points, res):
    out = dict(res)
    p = points[len(points) // 2]
    out[p] = dict(res[p], tlc_writes=res[p]["tlc_writes"] + 1)
    return out


def control_in_place(prog, points, res):
    got = cell.reference_results(prog.cell, points, ftype="bfloat16")
    return dict(zip(points, got))


def sound(prog, points, res):
    return res


CELLS = {"daily": ("paper-msr.daily", dict(traces=2, max_ops=1024,
                                           policies=["baseline", "ips_agc"])),
         # the paper's bursty mode, which no cell runs yet (PERF.md §7):
         # its traces are one sequential write stream from page 0, so two
         # differ only past the shorter one's volume (~1,200 ops for hm_1)
         "bursty": ("paper-msr.daily", dict(traces=2, max_ops=8192,
                                            policies=["ips", "coop"],
                                            mode="bursty"))}


def run(kind, fault):
    name, kw = CELLS[kind]
    c = benchtest.small_cell(name, **kw)
    out = cell.run_cell(c, seed=SEED, seconds=0.01, trace=False,
                        t_start=time.perf_counter(),
                        program=Broken(c, fault),
                        device={"platform": "cpu", "kind": "cpu",
                                "count": 1})
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_program_is_correct(kind):
    out = run(kind, sound)
    assert out["correct"] is True
    assert out["failed"] == 0
    assert out["checks"]["counter_mismatch"]["value"] == 0
    assert out["metrics"]["sim_ops_per_s"]["value"] > 0


FAULTS = [(kind, f) for kind in sorted(CELLS)
          for f in (unchanged_state, half_left_out, half_copied,
                    one_counter_altered, control_in_place)]


@pytest.mark.parametrize("kind,fault", FAULTS,
                         ids=[f"{k}-{f.__name__}" for k, f in FAULTS])
def test_fault_is_not_correct(kind, fault):
    out = run(kind, fault)
    assert out["correct"] is False
    assert out["failed"] > 0


def test_a_traced_run_without_device_readings_fails():
    """On the CPU the trace holds no device plane, so the device metrics
    read nothing: the run fails instead of leaving them out."""
    name, kw = CELLS["daily"]
    c = benchtest.small_cell(name, **kw)
    with pytest.raises(cell.MetricMissing, match="device_idle_share"):
        cell.run_cell(c, seed=SEED, seconds=0.01, trace=True,
                      t_start=time.perf_counter(), program=Broken(c, sound),
                      device={"platform": "cpu", "kind": "cpu", "count": 1})
