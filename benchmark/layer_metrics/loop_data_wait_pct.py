"""loop_data_wait_pct (run loop): the share of the window the run loop sat
blocked on an empty feeder queue — the program's own ``data_wait`` span
(``AsyncFeeder``'s consumer, around ``q.get()``).  The twin of
``data_wait_pct``, which the harness takes from outside around ``next()``."""

import program_spans


def read(view):
    return program_spans.share_pct(view, "data_wait")
