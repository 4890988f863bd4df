"""step_device_ms (compiled steps): the median duration of the step program
on the device, from the ``XLA Modules`` line of the traced window."""


def read(view):
    if not view.reduced or view.reduced["step_s"] is None:
        return None
    return 1e3 * view.reduced["step_s"]
