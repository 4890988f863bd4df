"""mfu_pct (models): the operations the model needs for the items the run
completed (``benchmark/flops.py``) over what the chips could do in the
window at their peak.  A view of ``throughput_per_chip``: the same number
times a constant of the configuration."""


def read(view):
    if not view.peaks:
        return None
    run = view.run
    return (100.0 * view.flops_per_item * run.items / run.window_s
            / (view.cell.chips * view.peaks["flops_per_s_bf16"]))
