"""loop_self_pct (run loop): the share of the window that is the ``step``
span's self time: an iteration of ``Trainer.train_epoch`` less its
``data_wait``, ``dispatch`` and ``host_sync`` children — the loop's own
overhead (the preemption poll, hooks, whatever waits for the interpreter
between the children, and the harness's ``step_begin`` and wrappers, which
run inside the iteration).  With the other three ``loop_*`` it adds up to
the window."""

import program_spans


def read(view):
    return program_spans.share_pct(view, "step", self_time=True)
