"""step_forward_ms (compiled steps): milliseconds a step of the device's
self time on instructions of the forward pass: no ``transpose(``, no
``rematted_computation``, not the update.  The loss is with it, and since
PR 33 the fused loss's gradient products, which its forward rule runs.
``scope_times.py`` joins the capture to the program's ``compiled_scopes``;
with the other phases and the ``scopes`` line's ``unknown`` it adds up to
the whole steps' busy time."""

import scope_times


def read(view):
    return scope_times.phase_ms(view, "forward")
