"""The plain reference a configuration names, and the default one."""
import copy
from concurrent.futures import ThreadPoolExecutor

import benchtest
import pytest

from benchlib import catalog, cell, reference, synth

SEED = 2 ** 31 + 21


def grouped_paper_reference(c, points):
    """The paper reference as `cell.reference_results` computed it before
    a configuration could name its own: points grouped by policy and
    mode, one `reference.simulate` call per group."""
    drive = reference.drive_of(c.config)
    groups = {}
    for i, p in enumerate(points):
        groups.setdefault((p.policy, p.mode), []).append(i)

    def one(key):
        policy, mode = key
        traces, wastes = [], []
        for i in groups[key]:
            p = points[i]
            recipe = c.traffic["traces"][p.trace]
            traces.append(synth.truncated(synth.build(
                p.trace, recipe, drive.n_logical, drive.total_pages, mode,
                p.seed), c.traffic.get("max_ops")))
            wastes.append(reference.agc_waste(recipe["stats"]))
        return groups[key], reference.simulate(drive, policy, mode, traces,
                                               wastes, "float32", None)

    out = [None] * len(points)
    with ThreadPoolExecutor(4) as ex:
        for idx, summ in ex.map(one, sorted(groups)):
            for i, s in zip(idx, summ):
                out[i] = s
    return out


def test_default_reference_gives_the_paper_summaries():
    c = benchtest.small_cell("paper-msr.daily", traces=2, max_ops=1024)
    assert "reference" not in c.config
    pts = cell.Program(c).points(c.traffic, [SEED, SEED + 1])
    got = cell.reference_results(c, pts)
    want = grouped_paper_reference(c, pts)
    assert len(got) == len(pts) == 16
    assert got == want


def test_a_missing_reference_is_an_error():
    c = benchtest.small_cell("paper-msr.daily")
    c.config = dict(c.config, reference="nowhere")
    with pytest.raises(FileNotFoundError):
        catalog.reference(c)


def test_the_paper_reference_builds_msr_recipes_only():
    c = benchtest.small_cell("paper-msr.daily", traces=1)
    c.traffic = copy.deepcopy(c.traffic)
    name = next(iter(c.traffic["traces"]))
    stats = c.traffic["traces"][name]["stats"]
    c.traffic["traces"][name] = {"kind": "phases", "label": name,
                                 "cycles": 1, "phases": [stats]}
    pts = cell.Program(c).points(c.traffic, [SEED])
    with pytest.raises(ValueError, match="msr recipes"):
        cell.reference_results(c, pts)


@pytest.mark.parametrize("point", [{"repeat": 2}, {"cache_frac": 0.5},
                                   {"hostcache": {"mode": "wb"}}])
def test_the_paper_reference_refuses_fields_it_does_not_simulate(point):
    c = benchtest.small_cell("paper-msr.daily", traces=1)
    c.config = dict(c.config, point=point)
    pts = cell.Program(c).points(c.traffic, [SEED])
    with pytest.raises(ValueError, match=next(iter(point))):
        cell.reference_results(c, pts)
