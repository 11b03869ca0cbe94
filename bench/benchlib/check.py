"""The comparison that decides `correct`.

A cell's answers are the per-cell result dicts the timed path returns.
Three numbers are compared, each with its limit from the traffic file:

* `cells_missing` — cells of the window's iterations with no result
  (limit 0);
* `counter_mismatch` — over the sampled cells, metrics that count whole
  events (pages written, erases, ops, ...) and differ from the plain
  reference, plus metrics present on one side only (limit 0: they are
  exact);
* `float_rel_gap` — over the sampled cells, the widest relative gap
  |program - reference| / max(|reference|, 1e-12) of the other metrics
  (means, ratios, accumulated milliseconds), which the program reduces
  in float32 on the device and the reference in float64 on the host.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

# metrics that count events: exact in float32 (< 2**24) on both sides
INT_METRICS = frozenset((
    "n_ops", "host_pages", "slc_writes", "tlc_writes", "migrations",
    "erases", "reprogram_host", "reprogram_agc", "reprogram_trad",
    "host_absorbed", "host_absorbed_w", "host_dev_ops", "host_flush_w",
    "host_evict_w"))
CHECKS = ("cells_missing", "counter_mismatch", "float_rel_gap")


@dataclasses.dataclass
class Comparison:
    counter_mismatch: int = 0
    float_rel_gap: float = 0.0
    bad_cells: int = 0
    worst: str = ""
    notes: List[str] = dataclasses.field(default_factory=list)


def compare(got: Sequence[Dict], want: Sequence[Dict],
            labels: Sequence[str]) -> Comparison:
    """Program results against reference results, cell by cell."""
    c = Comparison()
    for label, g, w in zip(labels, got, want):
        bad = 0
        for k in sorted(set(g) ^ set(w)):
            bad += 1
            c.notes.append(f"{label}: metric {k} on one side only")
        for k in sorted(set(g) & set(w)):
            a, b = float(g[k]), float(w[k])
            if k in INT_METRICS:
                if a != b:
                    bad += 1
                    c.notes.append(f"{label}: {k} {a!r} != {b!r}")
                continue
            rel = abs(a - b) / max(abs(b), 1e-12)
            if not rel <= c.float_rel_gap:      # NaN counts as widest
                c.float_rel_gap = rel if rel == rel else float("inf")
                c.worst = f"{label} {k}: {a!r} vs {b!r}"
        c.counter_mismatch += bad
        c.bad_cells += bad > 0
    return c


def verdict(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every compared number at or under its limit."""
    return all(readings[k] <= limits[k] for k in CHECKS)


def lines(readings: Dict[str, float], limits: Dict[str, float]) -> list:
    return [f"check {k} {readings[k]!r} limit {limits[k]!r}"
            for k in CHECKS]


def as_json(readings: Dict[str, float], limits: Dict[str, float]) -> dict:
    return {k: {"value": readings[k], "limit": limits[k]} for k in CHECKS}
