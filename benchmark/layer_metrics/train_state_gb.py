"""train_state_gb (train state): the bytes of the train state the step
carries from call to call (parameters, a model's non-gradient state, the
optimizer's moments), as the runner counts the arrays it made
(``state_bytes`` in its notes).  The part of ``hbm_peak_gb`` that stays
between steps; the rest is the step's temporaries.  A runner that gives no
such count leaves the metric out."""


def read(view):
    nbytes = view.run.notes.get("state_bytes")
    return None if nbytes is None else nbytes / 1e9
