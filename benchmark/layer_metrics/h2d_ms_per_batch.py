"""h2d_ms_per_batch (input): the median time of one call of
``DeviceFeeder._put`` inside the window — the harness's ``h2d`` span.  The
call stages the copy of a batch to the device and, for uint8 batches,
dispatches the normalisation; the copy itself ends later, on the device's
side, so this is the host's cost of a batch and not the wire's."""


import statistics


def read(view):
    run = view.run
    spent = view.cell.spans.durations("h2d", run.window_start,
                                      run.window_end)
    return 1e3 * statistics.median(spent) if spent else None
