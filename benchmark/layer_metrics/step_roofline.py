"""step_roofline (compiled steps, as the kernel layer): the least time
the chip could take for one step over the time the step took on the device.

The least time is the larger of the model's operations over the peak
FLOP/s (``benchmark/flops.py``: forward and backward, nothing recomputed)
and the bytes the step moves over the peak bytes/s.  The bytes are the
compiler's own count for the compiled step (``cost_analysis()["bytes
accessed"]``, one device's program): every operand and result of every
fused operation, so memory that stays in VMEM between two fusions is not
counted and a tensor read by two fusions is counted twice.  Which of the
two bounds it is said on an earlier line."""

import harness


def read(view):
    reduced, peaks, run = view.reduced, view.peaks, view.run
    if not reduced or reduced["step_s"] is None or not peaks \
            or run.compiler_bytes is None:
        return None
    flops = view.flops_per_item * run.notes["batch"] / view.cell.chips
    by_compute = flops / peaks["flops_per_s_bf16"]
    by_memory = run.compiler_bytes / peaks["hbm_bytes_per_s"]
    harness.say("roofline", bound="memory" if by_memory > by_compute
                else "compute", compute_ms=1e3 * by_compute,
                memory_ms=1e3 * by_memory, step_ms=1e3 * reduced["step_s"])
    return 100.0 * max(by_compute, by_memory) / reduced["step_s"]
