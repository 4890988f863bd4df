"""fused_ce_roofline (kernels): the least time the chip could take for one
step's differentiated loss (``fused_ce_cost.py``: three head products over
every loss row against the bytes of the head, the float32 gradient
accumulator and the logits chunks) over the self time a step spends on
instructions under the program's ``fused_ce`` scope, whatever their phase
(``scope_times.py``).  A step without that scope leaves the metric out."""

import fused_ce_cost
import harness
import scope_times


def read(view):
    found = scope_times.read(view)
    if found is None or not view.peaks:
        return None
    spent_ms = found["scope_ms"].get("fused_ce")
    if not spent_ms:
        return None
    floor_ms = 1e3 * fused_ce_cost.floor_seconds(
        view.cell.config, view.cell.traffic, view.peaks)
    harness.say("fused_ce", ms_per_step=spent_ms, floor_ms_per_step=floor_ms)
    return 100.0 * floor_ms / spent_ms
