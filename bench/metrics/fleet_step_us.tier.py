"""Device time per scanned step of the host-tier fleet program, over
every tier fleet of the window.

Σ `device.scan` durations over Σ their `t_scan`, for the fleets that ran
the tier pipeline (the span's `hostcache` argument names the tier's
spec), all policies together. Each scanned step runs the host tier and
then its `sub_ops` device sub-op slots through the engine core. Nothing
is read where no tier fleet's `device.scan` span is in the window."""


def read(run):
    secs = steps = 0
    for sp in run.spans:
        if sp["name"] == "device.scan" and sp["args"].get("hostcache"):
            secs += sp["dur_s"]
            steps += sp["args"]["t_scan"]
    return 1e6 * secs / steps if steps else None
