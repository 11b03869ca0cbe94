"""Share of the fleet lanes' scanned steps that carry a live trace op.

Live ops are the returned cells' `n_ops`; lanes are every group's cells,
pad cells included, times the steps its scan ran (`t_scan` from the
runner's per-group timings). A count: it repeats exactly for a seed."""


def read(run):
    live = lanes = 0
    for it in run.window.iterations:
        live += it.live_ops
        lanes += sum((g["cells"] + g["pad"]) * g["t_scan"]
                     for g in it.timings)
    return 100.0 * live / lanes if lanes else None
