"""loader_assemble_ms_per_batch (input): the median ``assemble`` span inside
the window — ``DataLoader._assemble``: copying the samples into the batch
array and flipping."""

import program_spans


def read(view):
    return program_spans.median_ms(view, "assemble")
