"""device_idle_pct (device): the share of the traced window in which no
operation ran on the device, averaged over the chips."""


def read(view):
    if not view.reduced:
        return None
    return 100.0 * (1.0 - view.reduced["busy_s"] / view.reduced["window_s"])
