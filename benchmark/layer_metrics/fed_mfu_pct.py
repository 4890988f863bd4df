"""fed_mfu_pct (models): ``mfu_pct``'s arithmetic where the run is fed from
files: the operations the model needs for the items the epoch completed
over what the chip could do in the window at its peak.  A view of
``fed_throughput_per_chip``: the same number times a constant of the
configuration, so a claim on it has a share of the peak beside it."""

import os

import harness

read = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "mfu_pct.py")).read
