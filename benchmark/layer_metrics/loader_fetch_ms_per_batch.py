"""loader_fetch_ms_per_batch (input): the median ``fetch`` span inside the
window — ``DataLoader.iter_batches`` around ``pool.map(...)``: decoding and
augmenting one batch's samples on the worker threads, as the producer
thread waits for it."""

import program_spans


def read(view):
    return program_spans.median_ms(view, "fetch")
