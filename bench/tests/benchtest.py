"""Shared set-up of the benchmark's CPU tests: import paths and small
copies of the cells."""
import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import catalog  # noqa: E402


def small_cell(name: str, *, traces: int = 2, max_ops: int = 1024,
               policies=None, mode=None) -> catalog.Cell:
    """A cell of the benchmark at a size a CPU test holds: the first
    `traces` traces, truncated to `max_ops` ops, optionally replayed in
    another mode."""
    c = catalog.load_cell(name)
    c.traffic = copy.deepcopy(c.traffic)
    if mode is not None:
        c.traffic["mode"] = mode
    c.traffic["traces"] = dict(list(c.traffic["traces"].items())[:traces])
    c.traffic["max_ops"] = max_ops
    if policies is not None:
        c.traffic["policies"] = list(policies)
    return c
