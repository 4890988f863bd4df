"""loop_dispatch_pct (run loop): the share of the window the run loop spent
in the call into the compiled step, as the host sees it — the ``dispatch``
span around ``self.train_step(...)`` in ``Trainer.train_epoch``.  The call
returns when the step is enqueued, or later where the device's queue is
full or another thread holds the interpreter."""

import program_spans


def read(view):
    return program_spans.share_pct(view, "dispatch")
