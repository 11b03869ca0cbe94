"""The cell `hostcache-wb.flush-burst` and its plain reference
(`bench/references/hostcache.py`): the reference equals the program under
write-only and always-allocate tiers, its bfloat16 control fails, a
reference that leaves the tier out fails, and the tier's per-layer
readers read a host-tier window and nothing in a paper window."""
import copy

import benchtest
import pytest

from benchlib import catalog, cell, check, window
from test_bench_hostcache import SEED, host_tier_window  # noqa: F401

CELL = "hostcache-wb.flush-burst"
POLICIES = ("baseline", "ips", "ips_agc", "coop")


def wb_cell(promote: str, max_ops: int = 2048) -> catalog.Cell:
    """The dm-writecache cell at a CPU size, its tier's promote gate set
    to `promote`."""
    c = benchtest.small_cell(CELL, max_ops=max_ops)
    c.config = copy.deepcopy(c.config)
    c.config["point"]["hostcache"]["promote"] = promote
    return c


@pytest.fixture(scope="module", params=["write", "always"])
def wb_answers(request):
    """The program's answers for one seed under all four policies."""
    c = wb_cell(request.param)
    prog = cell.Program(c)
    pts = prog.points(c.traffic, [SEED])
    res, _ = prog.sweep(pts)
    return c, pts, [res[p] for p in pts]


def ref_module():
    return catalog._module(benchtest.BENCH, "references", "hostcache")


def test_the_cell_runs_the_write_only_tier_of_dm_writecache():
    c = catalog.load_cell(CELL)
    assert c.config["reference"] == "hostcache"
    assert tuple(c.traffic["policies"]) == POLICIES
    pts = cell.Program(c).points(c.traffic, [1])
    hc = pts[0].hostcache
    assert (hc.mode, hc.promote, hc.flush, hc.wm_hi, hc.wm_lo) == \
        ("wb", "write", "watermark", 0.5, 0.45)
    assert ref_module().tier_of(c.config) == ref_module().Tier(
        **{f: getattr(hc, f) for f in ref_module().Tier._fields})


def test_the_tier_reference_matches_the_program(wb_answers):
    c, pts, got = wb_answers
    want = cell.reference_results(c, pts)
    cmp = check.compare(got, want, [p.key for p in pts])
    assert cmp.counter_mismatch == 0, cmp.notes[:5]
    assert cmp.float_rel_gap <= c.traffic["limits"]["float_rel_gap"]
    assert {p.policy for p in pts} == set(POLICIES)
    for g in got:
        # the mechanism does the work: the tier absorbs writes and
        # flushes in every cell
        assert g["host_flush_w"] > 0 and g["host_absorbed_w"] > 0
        assert g["host_absorbed"] + g["host_dev_ops"] == g["n_ops"]


def test_the_bfloat16_control_fails(wb_answers):
    c, pts, got = wb_answers
    want = cell.reference_results(c, pts, "bfloat16")
    cmp = check.compare(got, want, [p.key for p in pts])
    readings = {"cells_missing": 0, "counter_mismatch":
                cmp.counter_mismatch, "float_rel_gap": cmp.float_rel_gap}
    assert not check.verdict(readings, c.traffic["limits"])


def test_a_reference_without_the_tier_fails_on_the_device_counts(
        wb_answers):
    """The paper's golden drive on the same traces, with the program's
    own host counters copied in: the device saw another op stream, so
    its counts differ."""
    from benchlib import reference, synth
    c, pts, got = wb_answers
    ref = ref_module()
    drive = reference.drive_of(c.config)
    want = []
    for p, g in zip(pts, got):
        recipe = c.traffic["traces"][p.trace]
        tr = synth.truncated(synth.build(
            p.trace, recipe, drive.n_logical, drive.total_pages, p.mode,
            p.seed), c.traffic["max_ops"])
        s = reference.simulate(drive, p.policy, p.mode, [tr],
                               [ref.waste_of(recipe, drive, p.seed)])[0]
        want.append(s | {k: v for k, v in g.items() if k not in s})
    cmp = check.compare(got, want, [p.key for p in pts])
    assert cmp.bad_cells == len(pts)
    assert sum("slc_writes" in n for n in cmp.notes) == len(pts)


@pytest.mark.parametrize("group,match", [
    (None, "point.hostcache"),
    ({"mode": "wb", "lines": 1024}, "lines"),
    ({"mode": "wb", "promote": "never"}, "promote")])
def test_the_tier_reference_refuses_what_it_does_not_simulate(group,
                                                              match):
    c = wb_cell("write")
    pts = cell.Program(c).points(c.traffic, [1])
    if group is None:
        del c.config["point"]
    else:
        c.config["point"]["hostcache"] = group
    with pytest.raises(ValueError, match=match):
        cell.reference_results(c, pts)


def test_the_tier_reference_refuses_a_point_without_a_tier():
    c = benchtest.small_cell("paper-msr.daily", traces=1)
    pts = cell.Program(c).points(c.traffic, [1])
    with pytest.raises(ValueError, match="no host tier"):
        ref_module().results(wb_cell("write").config, c.traffic, pts)


@pytest.fixture(scope="module")
def paper_window():
    """One traced iteration of a paper cell: trimmed fleets, no tier."""
    c = benchtest.small_cell("paper-msr.daily", traces=1,
                             policies=("ips",))
    prog = cell.Program(c)
    tracer = prog.Tracer()
    with tracer.activate():
        win = window.run(
            lambda i, pts: prog.sweep(pts),
            lambda i: prog.points(c.traffic, [SEED]), seconds=0.0)
    return c, win, tracer.spans


TIER_READERS = ("fleet_step_us.tier", "tier_subop_useful_share")


@pytest.mark.parametrize("name", TIER_READERS)
def test_a_tier_reader_reads_a_host_tier_window(name, host_tier_window):
    c, win, spans = host_tier_window
    value = catalog.metric_reader(c.bench_dir, name)(
        cell.Run(c, win, spans, None))
    assert isinstance(value, float) and 0.0 < value
    if name == "tier_subop_useful_share":
        # the tier's device ops over every tier fleet's sub-op slots, as
        # the runner's own timings count the fleets
        work = sum(r["host_dev_ops"] + r["host_evict_w"]
                   + r["host_flush_w"] for it in win.iterations
                   for r in it.results.values())
        slots = sum((g["cells"] + g["pad"]) * g["t_scan"] * 4
                    for it in win.iterations for g in it.timings)
        assert value == pytest.approx(100.0 * work / slots)
        assert value < 100.0


@pytest.mark.parametrize("name", TIER_READERS)
def test_a_tier_reader_reads_nothing_in_a_paper_window(name, paper_window):
    c, win, spans = paper_window
    assert any(sp["name"] == "device.scan" for sp in spans)
    read = catalog.metric_reader(c.bench_dir, name)
    assert read(cell.Run(c, win, spans, None)) is None
    # nor in spans without the tier's arguments (a program before them)
    bare = [sp | {"args": {k: v for k, v in sp["args"].items()
                           if k not in ("hostcache", "sub_ops")}}
            for sp in spans]
    assert read(cell.Run(c, win, bare, None)) is None
