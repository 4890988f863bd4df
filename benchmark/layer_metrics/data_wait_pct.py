"""data_wait_pct (run loop): the share of the window the training loop
spent inside ``next()`` of the feeder's iterator — the harness's
``data_wait`` span, taken around it from outside."""


def read(view):
    run = view.run
    waited = view.cell.spans.total("data_wait", run.window_start,
                                   run.window_end)
    if not view.cell.spans.durations("data_wait", run.window_start,
                                     run.window_end):
        return None
    return 100.0 * waited / run.window_s
