"""The recipe check: the program's traces against the traffic file's own
copy of their recipe."""
import copy
import os

import benchtest
import pytest

from benchlib import catalog, cell, reference, synth


@pytest.fixture(scope="module")
def prog_and_drive():
    c = benchtest.small_cell("paper-msr.daily")
    return cell.Program(c), reference.drive_of(c.config)


TRAFFIC = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(benchtest.BENCH, "traffic")) if f.endswith(".json"))


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_recipes_match_the_program(name, prog_and_drive):
    """Every committed traffic file, cells or not, builds what the program
    builds (two traces of each)."""
    prog, drive = prog_and_drive
    traffic = catalog.load_json(os.path.join(benchtest.BENCH, "traffic",
                                             f"{name}.json"))
    traffic["traces"] = dict(list(traffic["traces"].items())[:2])
    cell.recipe_check(prog, traffic, drive, seed=2 ** 31 + 11)


@pytest.mark.parametrize("field,delta", [("write_ratio", 0.01),
                                         ("n_requests", 1),
                                         ("idle_ms", 5.0),
                                         ("skew", 0.1)])
def test_a_changed_msr_stat_fails(field, delta, prog_and_drive):
    prog, drive = prog_and_drive
    c = benchtest.small_cell("paper-msr.daily", traces=1)
    traffic = copy.deepcopy(c.traffic)
    name = next(iter(traffic["traces"]))
    traffic["traces"][name]["stats"][field] += delta
    with pytest.raises(cell.RecipeMismatch, match=name):
        cell.recipe_check(prog, traffic, drive, seed=3)


def test_stats_need_every_field():
    with pytest.raises(ValueError, match="skew"):
        synth.stats_tuple({f: 1 for f in synth.STATS_FIELDS
                           if f != "skew"})
