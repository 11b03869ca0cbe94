"""The paper's plain reference: the four SLC-cache policies with no host
tier, every trace built from its `msr` recipe.

The default of a configuration that names no reference. Points are
grouped by policy and mode; each group is one `reference.simulate` call,
the groups run side by side on host threads. The AGC waste of a trace is
the paper's calibration from its recipe's stats (`reference.agc_waste`).
A point that sets any other of its fields than trace, mode, policy and
seed (a configuration's `point` group) is refused: this reference does
not simulate it.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from benchlib import reference, synth

THREADS = 4
OWN_FIELDS = ("trace", "mode", "policy", "seed", "baseline")


def plain(point) -> None:
    """Refuse a point with a field this reference does not simulate."""
    for f in dataclasses.fields(point):
        v = getattr(point, f.name)
        if f.name not in OWN_FIELDS and v != f.default:
            raise ValueError(f"point {point.key}: the paper reference "
                             f"does not simulate {f.name}={v!r}")


def results(config: dict, traffic: dict, points: list,
            ftype: str = "float32", device=None) -> List[Dict]:
    drive = reference.drive_of(config)
    groups: Dict[tuple, list] = {}
    for i, p in enumerate(points):
        plain(p)
        groups.setdefault((p.policy, p.mode), []).append(i)

    def one(key):
        policy, mode = key
        idx = groups[key]
        traces, wastes = [], []
        for i in idx:
            p = points[i]
            recipe = traffic["traces"][p.trace]
            if recipe["kind"] != "msr":
                raise ValueError(f"trace {p.trace}: the paper reference "
                                 f"builds msr recipes, not "
                                 f"{recipe['kind']!r}")
            traces.append(synth.truncated(synth.build(
                p.trace, recipe, drive.n_logical, drive.total_pages, mode,
                p.seed), traffic.get("max_ops")))
            wastes.append(reference.agc_waste(recipe["stats"]))
        return idx, reference.simulate(drive, policy, mode, traces,
                                       wastes, ftype, device)

    out: List[Optional[Dict]] = [None] * len(points)
    with ThreadPoolExecutor(max(1, min(THREADS, len(groups)))) as ex:
        for idx, summ in ex.map(one, sorted(groups)):
            for i, s in zip(idx, summ):
                out[i] = s
    return out
