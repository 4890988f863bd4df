"""idle_unattributed_pct (device): over the traced seconds, the share of the
device's idle time outside programs during which the run loop's thread was
in none of its leaf spans (``data_wait``, ``dispatch``, ``host_sync``).

The leaf records are placed on the capture's clock (the ``bench:window``
event there against the harness's record of the same span), added to the
capture's host events as ``loop:<name>``, and ``trace_reduce.reduce`` books
each idle gap to the one that covers most of it; the table is printed as an
earlier ``idle_by_program_span`` line.  What stays under ``none`` is idle
time the loop spent in its own code (``loop_self_pct``'s part)."""

import harness
import program_spans
import trace_reduce


def read(view):
    rec = program_spans.recorder()
    if rec is None or not view.reduced or not view.run.trace_file:
        return None
    traced = [(s, e) for n, s, e in view.cell.spans.records if n == "window"]
    if len(traced) != 1:
        return None
    trace = trace_reduce.load(view.run.trace_file)
    offset = program_spans.capture_offset(
        trace["host"], harness.WINDOW_SPAN, traced[0][0])
    if offset is None:
        return None
    placed = program_spans.loop_events(rec.records(*traced[0]), offset)
    if not placed:
        return None
    trace["host"].extend(placed)
    gaps = trace_reduce.reduce(
        trace, span_prefix=program_spans.LOOP_PREFIX,
        window_span=harness.WINDOW_SPAN,
        step_program=view.run.notes.get("step_program"))["idle_gaps"]
    share = program_spans.unattributed_share(gaps)
    harness.say("idle_by_program_span", idle_gaps=gaps,
                loop_spans_placed=len(placed), clock_offset_s=offset)
    return None if share is None else 100.0 * share
