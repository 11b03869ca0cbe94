"""Share of the window's wall time spent building traces on the host.

Reads the program's `workload` spans (`trace.build`, `trace.parse`)
recorded during the window; a span nested in another workload span is
counted once, through its outermost one."""


def read(run):
    spans = run.spans
    total = 0.0
    for sp in spans:
        if sp["cat"] != "workload" or sp["dur_s"] <= 0:
            continue
        parent = sp["parent"]
        if parent is not None and spans[parent]["cat"] == "workload":
            continue
        total += sp["dur_s"]
    return 100.0 * total / run.window.seconds
