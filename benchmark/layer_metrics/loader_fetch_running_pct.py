"""loader_fetch_running_pct (input): of the time the loader's worker
threads spent inside a sample, the part they ran and did not wait (for the
interpreter, for I/O): the sum of ``sample_cpu_s`` over the sum of
``sample_wall_s``, the counts the window's ``fetch`` spans carry.  The
wall time is each sample's, taken on its worker with ``perf_counter``; the
CPU time is the worker threads' own clocks, read around the batch's
``pool.map``."""

import program_spans


def read(view):
    run = view.run
    sums = program_spans.field_sums(
        program_spans.window_records(view), "fetch", run.window_start,
        run.window_end, ("sample_cpu_s", "sample_wall_s"))
    if not sums or sums["sample_wall_s"] <= 0:
        return None
    return 100.0 * sums["sample_cpu_s"] / sums["sample_wall_s"]
