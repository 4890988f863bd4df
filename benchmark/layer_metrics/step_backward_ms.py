"""step_backward_ms (compiled steps): milliseconds a step of the device's
self time on instructions of the backward pass (``transpose(`` in the
path, and no ``rematted_computation``).
``scope_times.py`` joins the capture to the program's ``compiled_scopes``;
with the other phases and the ``scopes`` line's ``unknown`` it adds up to
the whole steps' busy time."""

import scope_times


def read(view):
    return scope_times.phase_ms(view, "backward")
