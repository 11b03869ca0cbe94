"""A configuration, a traffic mix, a cell and a per-layer metric are added
with new files and new entries alone: no existing file changes."""
import hashlib
import json
import os
import shutil

import benchtest

from benchlib import catalog, cell


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


def test_addition_needs_no_edit(tmp_path):
    root = tmp_path / "co"
    shutil.copytree(benchtest.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(benchtest.ROOT, "BENCHMARK.json"), root)
    before = _digests(root / "bench")

    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "paper-msr.json").read_text())
    cfg["name"] = "paper-msr-half"
    cfg["drive"]["slc_cache_gb"] /= 2
    (bench / "configs" / "paper-msr-half.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "msr-daily.json").read_text())
    tr["name"] = "msr-daily-hm0"
    tr["traces"] = {"hm_0": tr["traces"]["hm_0"]}
    (bench / "traffic" / "msr-daily-hm0.json").write_text(json.dumps(tr))
    (bench / "metrics" / "live_ops_per_iteration.py").write_text(
        "def read(run):\n"
        "    its = run.window.iterations\n"
        "    return sum(it.live_ops for it in its) / len(its)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "paper-msr-half", "source": "test",
                            "file": "bench/configs/paper-msr-half.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "paper-msr-half.hm0",
                              "config": "paper-msr-half",
                              "traffic": "msr-daily-hm0", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "live_ops_per_iteration",
                              "unit": "ops", "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "sim_ops_per_s",
                              "workloads": ["paper-msr-half.hm0"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before

    c = catalog.load_cell("paper-msr-half.hm0", root=str(root))
    assert list(c.traffic["traces"]) == ["hm_0"]
    assert c.config["drive"]["slc_cache_gb"] == cfg["drive"]["slc_cache_gb"]
    assert [m.name for m in c.per_layer][-1] == "live_ops_per_iteration"
    win = cell.window.Window(0.0, 2.0, [cell.window.Iteration(
        0, 0.0, 2.0, ["p"], {"p": {"n_ops": 7}}, [])])
    got = catalog.read_metrics(c, cell.Run(c, win, [], None))
    assert got["live_ops_per_iteration"] == {"value": 7.0, "unit": "ops"}
    # the device readers find nothing to read without a trace
    assert "device_idle_share" not in got


def test_configurations_state_the_lengths_their_traffic_runs():
    """Each configuration lists the request count of every trace its
    cells run, as run, beside the published count it was cut from."""
    spec = catalog.load_json(os.path.join(catalog.ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        c = catalog.load_cell(w["name"])
        for name, recipe in c.traffic["traces"].items():
            assert c.config["trace_requests"][name] == \
                recipe["stats"]["n_requests"]
            assert c.config["published"]["trace_requests"][name] > \
                recipe["stats"]["n_requests"]
