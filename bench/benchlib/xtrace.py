"""From a profiler trace to device busy and idle time, the period of the
loop the device ran, the busiest device operations and the longest idle
gaps.

`events(path)` flattens a `.xplane.pb` into plain records
(plane, line, name, start_ns, end_ns); `reduce(...)` works on those
records alone, so a test can hand it a recorded or a built list.

* Device planes are the planes named `/device:<kind>:<n>` other than
  the CPU. Their `XLA Ops` line holds one event per operation run; their
  `XLA Modules` line one event per program run. An operation that holds
  others (a `while` holds its body) is not counted itself: busy time is
  the union of the innermost operations' intervals (the module intervals
  where a plane has no operation line) inside the traced window,
  averaged over the planes.
* The traced window runs from the first to the last host event named
  `window_mark` (the benchmark's annotation); without one, from the
  first to the last device event.
* Loop period: a compiled loop runs each operation of its body once per
  trip, so those operations recur equally often inside the window. An
  operation's runs are split where a program run begins or ends between
  two of them (each run of the loop lies in one program run), and its
  period is (last start - first start) / (runs - 1) over the largest
  such group. The loop period is the median of that over the
  operations whose group is within one of the median group of the
  frequent ones, averaged over the planes.
* The busiest operations are the operation events by name (the HLO
  instruction's name, without its text), or the programs where the
  trace holds modules only.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def program_of(module: str) -> str:
    """`jit__run_fleet(1234)` -> `_run_fleet`: a module event's program."""
    name = module.split("(", 1)[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith(
        "/device:CPU")


def events(path: str, keep_host: Sequence[str] = ()) -> List[Event]:
    """Device events, and host events whose name starts with one of
    `keep_host`, from one `.xplane.pb` file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(tuple(keep_host)):
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns),
                                 float(ev.start_ns + ev.duration_ns)))
    return out


def union_ns(intervals: Iterable[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of the union of intervals, clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Iterable[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """The complement of the intervals' union inside [lo, hi]."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # averaged over device planes
    planes: int
    loop_period_s: Optional[float]      # averaged over planes
    top_ops: List[Tuple[str, float]]    # summed over planes
    idle_gaps: List[Tuple[float, float]]  # (start_ns, end_ns), longest first
    lo_ns: float
    hi_ns: float

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[11]{0} fusion(...)` -> `%fusion.12`."""
    return event_name.split(" = ", 1)[0]


def innermost(ops: Sequence[Event]) -> List[Event]:
    """The operations that hold no other: sorted by start (the longer
    first), an operation holds others where the next one lies inside
    it."""
    order = sorted(ops, key=lambda e: (e.start_ns, -e.end_ns))
    return [e for e, nxt in zip(order, order[1:] + [None])
            if nxt is None or nxt.start_ns >= e.end_ns
            or nxt.end_ns > e.end_ns]


def loop_period_ns(ops: Sequence[Event], bounds: Sequence[float] = (),
                   min_count: int = 100) -> Optional[float]:
    """Period of the loop whose body runs most often among `ops` (module
    docstring); `bounds` are the starts and ends of program runs. None
    where no operation recurs `min_count` times in one program run."""
    cuts = sorted(bounds)
    starts: Dict[str, List[float]] = defaultdict(list)
    for e in ops:
        starts[e.name].append(e.start_ns)
    runs: Dict[str, List[float]] = {}
    for name, s in starts.items():
        s.sort()
        best, cur = [], [s[0]]
        for a, b in zip(s, s[1:]):
            if bisect.bisect_right(cuts, a) != bisect.bisect_right(cuts, b):
                best, cur = max(best, cur, key=len), []
            cur.append(b)
        best = max(best, cur, key=len)
        if len(best) >= min_count:
            runs[name] = best
    if not runs:
        return None
    ref = statistics.median(len(s) for s in runs.values())
    body = [s for s in runs.values() if abs(len(s) - ref) <= 1]
    return statistics.median((s[-1] - s[0]) / (len(s) - 1) for s in body)


def reduce(evs: Sequence[Event], *, window_mark: Optional[str] = None,
           top: int = 10) -> Reduction:
    """Busy and idle time, the loop period and the busiest operations
    inside the traced window (module docstring)."""
    dev = [e for e in evs if is_device_plane(e.plane)]
    if not dev:
        raise ValueError("the trace holds no device events")
    marks = [e for e in evs if window_mark is not None
             and not is_device_plane(e.plane) and e.name == window_mark]
    if marks:
        lo = min(e.start_ns for e in marks)
        hi = max(e.end_ns for e in marks)
    else:
        lo = min(e.start_ns for e in dev)
        hi = max(e.end_ns for e in dev)
    by_plane: Dict[str, Dict[str, list]] = defaultdict(
        lambda: defaultdict(list))
    for e in dev:
        by_plane[e.plane][e.line].append(e)
    busy, ops_time = 0.0, defaultdict(float)
    periods: List[float] = []
    first_busy: Optional[list] = None
    for plane, lines in sorted(by_plane.items()):
        ops = innermost(lines.get(OPS_LINE, []))
        busy_evs = ops or lines.get(MODULES_LINE) or []
        iv = [(e.start_ns, e.end_ns) for e in busy_evs]
        busy += union_ns(iv, lo, hi)
        if first_busy is None:
            first_busy = iv
        period = loop_period_ns(
            [e for e in ops if lo <= e.start_ns and e.end_ns <= hi],
            [t for e in lines.get(MODULES_LINE, [])
             for t in (e.start_ns, e.end_ns)])
        if period is not None:
            periods.append(period)
        for e in busy_evs:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                name = (op_name(e.name) if e.line == OPS_LINE
                        else program_of(e.name))
                ops_time[name] += d / 1e9
    gaps = sorted(gaps_ns(first_busy or [], lo, hi),
                  key=lambda g: g[0] - g[1])
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy / len(by_plane) / 1e9,
        planes=len(by_plane),
        loop_period_s=(sum(periods) / len(periods) / 1e9
                       if len(periods) == len(by_plane) else None),
        top_ops=sorted(ops_time.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=gaps[:top], lo_ns=lo, hi_ns=hi)


def describe(evs: Sequence[Event], top: int = 8) -> str:
    """One line on what a trace holds: events per plane and line, and the
    most frequent program names, so a metric that reads nothing shows
    why."""
    lines: Dict[Tuple[str, str], int] = defaultdict(int)
    progs: Dict[str, int] = defaultdict(int)
    for e in evs:
        lines[(e.plane, e.line)] += 1
        if e.line == MODULES_LINE:
            progs[program_of(e.name)] += 1
    where = ", ".join(f"{p} [{ln}] {n}" for (p, ln), n in sorted(
        lines.items())) or "no events"
    most = ", ".join(f"{p} x{n}" for p, n in sorted(
        progs.items(), key=lambda kv: -kv[1])[:top]) or "none"
    return f"{where}; programs: {most}"


def name_gaps(gaps: Sequence[Tuple[float, float]],
              host_spans: Sequence[Tuple[str, float, float, int]]
              ) -> List[Tuple[str, float]]:
    """Name each idle gap by the deepest host span open at its middle.
    `host_spans` are (name, start_ns, end_ns, depth) on the trace's
    clock; a gap with no span open is named `host`."""
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [sp for sp in host_spans if sp[1] <= mid < sp[2]]
        name = max(open_, key=lambda sp: sp[3])[0] if open_ else "host"
        out.append((name, (e - s) / 1e9))
    return out
