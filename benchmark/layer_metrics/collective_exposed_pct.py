"""collective_exposed_pct (parallelism): the part of the collective time
during which no other operation ran on that device, as a share of the
step program's time on the device."""


def read(view):
    if not view.reduced or not view.reduced["steps_total_s"]:
        return None
    return (100.0 * view.reduced["collective_exposed_s"]
            / view.reduced["steps_total_s"])
