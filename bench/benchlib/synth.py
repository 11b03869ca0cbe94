"""The benchmark's own trace generator: page-level op tensors from a recipe.

A copy of the program's statistical synthesizer and request-to-page
expansion, kept here so that the traffic is a yardstick the program
cannot change: the plain reference builds every trace it checks from
the recipe in the traffic file, through this module, and never from
the program's tensors. The arithmetic is the program's own, step for
step (numpy RNG stream, f32/i32/i8 casts, tail-only padding), so a sound
program builds bit-identical tensors.

A recipe is a dict from a traffic file:

  {"kind": "msr", "stats": {...TraceStats fields...}}
  {"kind": "phases", "label": "...", "cycles": n,
   "phases": [{...TraceStats fields...}, ...]}

A `phases` recipe is the program's phase synthesizer: the `phases` list
repeated `cycles` times, phase `i` drawn with the label `{label}.{i}`,
each phase starting 1 ms after the previous one's last arrival.

`build(name, recipe, n_logical, capacity_pages, mode, seed)` returns the
padded op dict (arrival_ms f32, lba i32, is_write i8, n_ops).
"""
from __future__ import annotations

import zlib
from typing import Optional

import numpy as np

STATS_FIELDS = ("n_requests", "write_ratio", "mean_req_pages", "seq_prob",
                "working_set_frac", "skew", "interarrival_ms", "idle_every",
                "idle_ms")
PAD_OPS = 1 << 17


def stats_tuple(stats: dict) -> tuple:
    """The recipe's TraceStats as a tuple in field order (ints as ints)."""
    missing = [f for f in STATS_FIELDS if f not in stats]
    if missing:
        raise ValueError(f"trace stats lack {missing}")
    return tuple(int(stats[f]) if f in ("n_requests", "idle_every")
                 else float(stats[f]) for f in STATS_FIELDS)


def _zipf_like(rng, n, size, skew):
    u = rng.random(size)
    idx = np.floor(n * u ** skew).astype(np.int64)
    return np.clip(idx, 0, n - 1)


def requests(stats: dict, n_logical: int, seed: int, capacity_pages: int,
             label: str) -> dict:
    """Request-level trace (arrival_ms, lba, pages, is_write)."""
    (n, write_ratio, mean_req_pages, seq_prob, ws_frac, skew,
     interarrival_ms, idle_every, idle_ms) = stats_tuple(stats)
    rng = np.random.default_rng(
        zlib.crc32(f"{label}/{seed}".encode()) % (2 ** 31))
    cap = capacity_pages or n_logical
    ws = max(int(cap * ws_frac), 1024)
    ws = min(ws, int(n_logical * 0.9))
    base = rng.integers(0, max(n_logical - ws, 1))
    is_write = rng.random(n) < write_ratio
    sizes = np.clip(rng.poisson(mean_req_pages, n), 1, 16)
    seq = rng.random(n) < seq_prob
    rand_targets = base + _zipf_like(rng, ws, n, skew)
    lba = np.empty(n, np.int64)
    cursor = base
    for i in range(n):
        lba[i] = cursor if seq[i] else rand_targets[i]
        cursor = (lba[i] + sizes[i]) % (n_logical - 16)
    gaps = rng.exponential(interarrival_ms, n)
    idle_mask = (np.arange(n) % idle_every) == idle_every - 1
    gaps = gaps + idle_mask * idle_ms
    arrival = np.cumsum(gaps) - gaps[0]
    return {"arrival_ms": arrival, "lba": lba, "pages": sizes,
            "is_write": is_write}


def phases(recipe: dict, n_logical: int, seed: int,
           capacity_pages: int) -> dict:
    """Request-level trace of a `phases` recipe: its phases tiled along
    the arrival axis."""
    seq = list(recipe["phases"]) * int(recipe["cycles"])
    if not seq:
        raise ValueError("a phases recipe needs at least one phase")
    parts, offset = [], 0.0
    for i, stats in enumerate(seq):
        req = requests(stats, n_logical, seed, capacity_pages,
                       label=f"{recipe['label']}.{i}")
        arrival = req["arrival_ms"] + offset
        if len(arrival):
            offset = float(arrival[-1]) + 1.0
        parts.append(req | {"arrival_ms": arrival})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def bursty(req: dict, n_logical: int) -> dict:
    """The write volume as back-to-back sequential 32 KB writes."""
    total = int(np.asarray(req["pages"])[
        np.asarray(req["is_write"], bool)].sum())
    total = max(total, 8)
    n_req = total // 8
    return {"arrival_ms": np.zeros(n_req),
            "lba": (np.arange(n_req) * 8) % (n_logical - 8),
            "pages": np.full(n_req, 8), "is_write": np.ones(n_req, bool)}


def expand(req: dict, n_logical: int) -> dict:
    """Requests to page ops, padded to a PAD_OPS multiple with tail pads."""
    counts = np.asarray(req["pages"], np.int64)
    o = int(counts.sum())
    arrival = np.repeat(req["arrival_ms"], counts).astype(np.float32)
    offs = (np.concatenate([np.arange(c) for c in counts]) if o
            else np.zeros(0, np.int64))
    lba = np.repeat(np.asarray(req["lba"], np.int64), counts) + offs
    lba = (lba % n_logical).astype(np.int32)
    is_write = np.repeat(req["is_write"], counts).astype(np.int8)
    target = max(PAD_OPS, -(-o // PAD_OPS) * PAD_OPS)
    pad = target - o
    last_t = arrival[-1] if o else np.float32(0.0)
    return {"arrival_ms": np.concatenate(
                [arrival, np.full(pad, last_t, np.float32)]),
            "lba": np.concatenate([lba, np.zeros(pad, np.int32)]),
            "is_write": np.concatenate([is_write,
                                        np.full(pad, -1, np.int8)]),
            "n_ops": o}


def truncated(tr: dict, max_ops: Optional[int]) -> dict:
    """The first `max_ops` ops of a padded op dict (all of it for None)."""
    if max_ops is None:
        return tr
    return {k: (v[:max_ops] if isinstance(v, np.ndarray) else v)
            for k, v in tr.items()} | {"n_ops": min(tr["n_ops"], max_ops)}


def build(name: str, recipe: dict, n_logical: int, capacity_pages: int,
          mode: str, seed: int) -> dict:
    """Padded op tensors of one named trace under `mode` and `seed`."""
    if recipe["kind"] == "msr":
        req = requests(recipe["stats"], n_logical, seed, capacity_pages,
                       label=name)
    elif recipe["kind"] == "phases":
        req = phases(recipe, n_logical, seed, capacity_pages)
    else:
        raise ValueError(f"trace {name}: unknown recipe kind "
                         f"{recipe['kind']!r}")
    if mode == "bursty":
        req = bursty(req, n_logical)
    elif mode != "daily":
        raise ValueError(f"unknown mode {mode!r}")
    return expand(req, n_logical)
