"""feeder_put_ms_per_batch (input): the median ``put`` span inside the
window — the whole body of ``DeviceFeeder._put``: staging the copy of a
batch and, for uint8 batches, dispatching the normalisation.  The twin of
``h2d_ms_per_batch``, whose harness span encloses it."""

import program_spans


def read(view):
    return program_spans.median_ms(view, "put")
