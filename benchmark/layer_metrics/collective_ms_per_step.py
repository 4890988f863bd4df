"""collective_ms_per_step (parallelism): per device, the union of the
intervals in which a collective operation ran or was in flight, over the
steps of the traced window."""


def read(view):
    if not view.reduced or not view.reduced["steps"]:
        return None
    return 1e3 * view.reduced["collective_s"] / view.reduced["steps"]
