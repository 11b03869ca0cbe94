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


def flush_burst_traffic() -> dict:
    """The program's flush_burst scenario as a phases recipe (test data,
    not a cell)."""
    return catalog.load_json(os.path.join(
        benchtest.BENCH, "tests", "data", "flush-burst-daily.json"))


@pytest.mark.parametrize("mode,seed", [("daily", 0),
                                       ("daily", 2 ** 31 + 7),
                                       ("daily", 4_000_000_037),
                                       ("bursty", 2 ** 31 + 7)])
def test_phases_recipe_matches_the_program(mode, seed, prog_and_drive):
    prog, drive = prog_and_drive
    traffic = flush_burst_traffic() | {"mode": mode}
    cell.recipe_check(prog, traffic, drive, seed=seed)


def _phase_field(recipe, i, field, delta):
    recipe["phases"][i][field] += delta


def _top_field(recipe, field, delta):
    recipe[field] = recipe[field] + delta


@pytest.mark.parametrize("change", [
    lambda r: _phase_field(r, 0, "write_ratio", 0.01),
    lambda r: _phase_field(r, 1, "n_requests", 1),
    lambda r: _phase_field(r, 1, "idle_ms", 5.0),
    lambda r: _phase_field(r, 0, "skew", 0.1),
    lambda r: _top_field(r, "cycles", -1),
    lambda r: _top_field(r, "label", "x"),
    lambda r: r["phases"].reverse()],
    ids=["day-write_ratio", "night-n_requests", "night-idle_ms",
         "day-skew", "cycles", "label", "phase-order"])
def test_a_changed_phases_recipe_fails(change, prog_and_drive):
    prog, drive = prog_and_drive
    traffic = flush_burst_traffic()
    change(traffic["traces"]["flush_burst"])
    with pytest.raises(cell.RecipeMismatch, match="flush_burst"):
        cell.recipe_check(prog, traffic, drive, seed=3)


def test_phases_recipe_counts_its_requests():
    recipe = flush_burst_traffic()["traces"]["flush_burst"]
    req = synth.phases(recipe, 1 << 16, 0, 1 << 20)
    assert len(req["arrival_ms"]) == 6 * (2600 + 400)
    with pytest.raises(ValueError, match="at least one phase"):
        synth.phases(dict(recipe, phases=[]), 1 << 16, 0, 1 << 20)
