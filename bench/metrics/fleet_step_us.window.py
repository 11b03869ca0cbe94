"""Device time per scanned step of the trimmed fleet program, over every
fleet of the window.

Σ `device.scan` durations over Σ their `t_scan`, for the fleets that ran
the trimmed program (`t_scan < t_len`), all policies together. A
`device.scan` span runs from when the fleet's scan program could start
to when its result was ready on the device, so it holds the program's
launch and its pad replay besides the scan itself. Nothing is read
where no such fleet's `device.scan` span is in the window."""


def read(run):
    secs = steps = 0
    for sp in run.spans:
        if sp["name"] != "device.scan":
            continue
        a = sp["args"]
        if a["t_scan"] < a["t_len"]:
            secs += sp["dur_s"]
            steps += a["t_scan"]
    return 1e6 * secs / steps if steps else None
