"""A configuration, a traffic mix, a cell and a per-layer metric are added
with new files and new entries alone: no existing file changes."""
import hashlib
import json
import os
import shutil

import benchtest
import pytest

from benchlib import catalog, cell, reference


def _digests(root):
    out = {}
    for base, _, names in os.walk(root):
        for n in names:
            p = os.path.join(base, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_every_cell_loads_with_its_files():
    spec = catalog.load_json(os.path.join(benchtest.ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        c = catalog.load_cell(w["name"])
        assert c.traffic["name"] == w["traffic"]
        assert c.config["name"] == w["config"]
        for m in c.per_layer:
            assert callable(catalog.metric_reader(c.bench_dir, m.name))
        for k in ("mode", "policies", "traces", "limits",
                  "reference_cells", "trace_seconds"):
            assert k in c.traffic


STUB_REFERENCE = (
    "def results(config, traffic, points, ftype, device):\n"
    "    return [{'n_ops': 0, 'trace': p.trace} for p in points]\n")


def _host_tier_files(bench):
    """A host-tier configuration naming its own reference, and a phases
    traffic mix (the program's flush_burst scenario)."""
    cfg = json.loads((bench / "configs" / "paper-msr.json").read_text())
    cfg.update(name="paper-msr-tier", reference="paper-msr-tier",
               point={"hostcache": {"mode": "wb", "flush": "watermark",
                                    "sets": 128, "ways": 8}})
    (bench / "configs" / "paper-msr-tier.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "tests" / "data" / "flush-burst-daily.json",
                bench / "traffic" / "flush-burst-daily.json")
    (bench / "references" / "paper-msr-tier.py").write_text(STUB_REFERENCE)
    return cfg, "paper-msr-tier", "flush-burst-daily"


def _paper_files(bench):
    """A configuration and traffic mix cut from the paper cell's."""
    cfg = json.loads((bench / "configs" / "paper-msr.json").read_text())
    cfg["name"] = "paper-msr-half"
    cfg["drive"]["slc_cache_gb"] /= 2
    (bench / "configs" / "paper-msr-half.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "msr-daily.json").read_text())
    tr["name"] = "msr-daily-hm0"
    tr["traces"] = {"hm_0": tr["traces"]["hm_0"]}
    (bench / "traffic" / "msr-daily-hm0.json").write_text(json.dumps(tr))
    return cfg, "paper-msr-half", "msr-daily-hm0"


@pytest.mark.parametrize("kind", ["paper", "host_tier"])
def test_addition_needs_no_edit(tmp_path, kind):
    root = tmp_path / "co"
    shutil.copytree(benchtest.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(benchtest.ROOT, "BENCHMARK.json"), root)
    before = _digests(root / "bench")

    bench = root / "bench"
    cfg, cfg_name, traffic = (_paper_files if kind == "paper"
                              else _host_tier_files)(bench)
    name = f"{cfg_name}.{traffic}"
    (bench / "metrics" / "live_ops_per_iteration.py").write_text(
        "def read(run):\n"
        "    its = run.window.iterations\n"
        "    return sum(it.live_ops for it in its) / len(its)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": cfg_name, "source": "test",
                            "file": f"bench/configs/{cfg_name}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": name, "config": cfg_name,
                              "traffic": traffic, "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "live_ops_per_iteration",
                              "unit": "ops", "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "sim_ops_per_s",
                              "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before

    c = catalog.load_cell(name, root=str(root))
    assert c.config == cfg
    assert c.traffic["name"] == traffic
    assert [m.name for m in c.per_layer][-1] == "live_ops_per_iteration"
    prog = cell.Program(c)
    pts = prog.points(c.traffic, [5])
    assert len(pts) == len(c.traffic["policies"]) * len(c.traffic["traces"])
    if kind == "paper":
        assert {p.hostcache for p in pts} == {None}
        assert catalog.reference(c).__module__ == "bench_references_paper"
    else:
        from repro.hostcache.spec import HostCacheSpec
        assert {p.hostcache for p in pts} == {
            HostCacheSpec(**cfg["point"]["hostcache"])}
        assert catalog.reference(c)(c.config, c.traffic, pts, "float32",
                                    None) == [
            {"n_ops": 0, "trace": "flush_burst"}] * len(pts)
        # the phases recipe builds what the program builds
        cell.recipe_check(prog, c.traffic, reference.drive_of(c.config),
                          seed=2 ** 31 + 17)
    win = cell.window.Window(0.0, 2.0, [cell.window.Iteration(
        0, 0.0, 2.0, ["p"], {"p": {"n_ops": 7}}, [])])
    got = catalog.read_metrics(c, cell.Run(c, win, [], None))
    assert got["live_ops_per_iteration"] == {"value": 7.0, "unit": "ops"}
    # the device readers find nothing to read without a trace
    assert "device_idle_share" not in got


def _n_requests(recipe: dict) -> int:
    """Host requests a trace recipe draws, before any mode rewrites them."""
    if recipe["kind"] == "phases":
        return int(recipe["cycles"]) * sum(
            int(st["n_requests"]) for st in recipe["phases"])
    return int(recipe["stats"]["n_requests"])


def test_configurations_state_the_lengths_their_traffic_runs():
    """Each configuration lists the request count of every trace its
    cells run, as run, beside the published count it was cut from."""
    spec = catalog.load_json(os.path.join(catalog.ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        c = catalog.load_cell(w["name"])
        for name, recipe in c.traffic["traces"].items():
            assert c.config["trace_requests"][name] == \
                _n_requests(recipe)
            assert c.config["published"]["trace_requests"][name] > \
                _n_requests(recipe)
