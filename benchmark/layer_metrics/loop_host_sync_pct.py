"""loop_host_sync_pct (run loop): the share of the window in the run loop's
host-side drains — the ``host_sync`` spans around ``meters.update``,
``obs.log_step`` and ``meters.maybe_display`` (the display converts device
scalars every ``print_freq`` steps, and waits for the step that made
them)."""

import program_spans


def read(view):
    return program_spans.share_pct(view, "host_sync")
