"""Device time per scanned step of the trimmed fleet program.

The traced slice lies in the first fleet dispatch of an iteration
(`cell.SliceTrace`), and the loop the device runs there is that
fleet's scan: the period of its trips (`xtrace.loop_period_ns`) is the
device time of one step of every cell of the fleet, launch gaps
included. Nothing is read unless that fleet ran the trimmed program (its
scan stopped short of its padded length, `t_scan < t_len`)."""


def read(run):
    g = run.traced_group
    if run.device is None or run.device.loop_period_s is None or g is None:
        return None
    if not g["t_scan"] < g["t_len"]:
        return None
    return 1e6 * run.device.loop_period_s
