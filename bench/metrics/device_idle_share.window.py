"""Share of the window's wall time in which the device held no fleet
work, over every iteration of the window.

Reads the program's `device` spans: each fleet's `device.scan` (its scan
program, pad replay included) and `device.tail` (latency padding, the
eager flush and summary), which the sweep runner stamps on the host clock
when each is ready on the device. The tail is an occupancy interval, an
upper bound on busy time, so this share is a lower bound on idle time.
The dispatch ramp before a fleet's scan is enqueued counts as idle: a
profiler slice of it found the device ~95% idle there (48 ms of ~51 ms).
Nothing is read where the window holds no `device` spans."""


def read(run):
    busy = [sp["dur_s"] for sp in run.spans if sp["cat"] == "device"]
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy) / run.window.seconds)
