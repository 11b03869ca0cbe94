"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(run):
    if run.device is None or run.device.window_s <= 0:
        return None
    return 100.0 * run.device.idle_share
