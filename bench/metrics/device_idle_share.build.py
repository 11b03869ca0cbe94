"""Share of the window's wall time spent building traces on the host
while the device held no fleet work.

The time inside outermost `workload` spans (as `trace_build_share`
counts them) that no `device` span (`device.scan`, `device.tail`)
covers, over the window's wall time: the part of trace building that
does not overlap the device. Nothing is read where the window holds no
`device` spans."""


def _covered(lo, hi, busy):
    return sum(max(0.0, min(hi, e) - max(lo, s)) for s, e in busy)


def read(run):
    spans = run.spans
    busy = [(sp["t0_s"], sp["t0_s"] + sp["dur_s"]) for sp in spans
            if sp["cat"] == "device"]
    if not busy:
        return None
    idle = 0.0
    for sp in spans:
        if sp["cat"] != "workload" or sp["dur_s"] <= 0:
            continue
        parent = sp["parent"]
        if parent is not None and spans[parent]["cat"] == "workload":
            continue
        lo, hi = sp["t0_s"], sp["t0_s"] + sp["dur_s"]
        idle += (hi - lo) - _covered(lo, hi, busy)
    return 100.0 * idle / run.window.seconds
