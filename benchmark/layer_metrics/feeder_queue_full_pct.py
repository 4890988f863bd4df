"""feeder_queue_full_pct (input): the share of the window the feeder's
producer thread sat blocked on a full queue — the ``queue_full`` span
around ``offer(...)`` in ``AsyncFeeder``.  Near 0: the producer sets the
pace; large: the run loop does."""

import program_spans


def read(view):
    return program_spans.share_pct(view, "queue_full")
