"""flash_attn_roofline (kernels): over the traced window, the least time
the chip could take for the attention kernels' calls over the time they
took on the device.

The calls are the ``XLA Ops`` events of the capture named ``attn.<n>`` (a
Mosaic call carries its scope's name; ``trace_reduce.reduce`` keeps ten
names, so the capture is read again).  Each call's floor is the larger of
its operations over the peak FLOP/s and its bytes over the peak bytes/s
(``attention_cost.py``: what the call computes, causal, from the shapes of
what it produces and the configuration's head sizes).  An earlier line says
the kernels' milliseconds a step and the calls by kind.  A capture with no
such call (another attention path, or no capture) leaves the metric out."""

import os

import attention_cost
import harness
import trace_reduce


def read(view):
    run, peaks = view.run, view.peaks
    if not run.trace_file or not peaks \
            or not os.path.exists(run.trace_file):
        return None
    trace = trace_reduce.load(run.trace_file)
    spans = [(s, e) for s, e, n, _ in trace["host"]
             if n == harness.WINDOW_SPAN]
    if len(spans) != 1:
        return None
    lo, hi = spans[0]
    program = run.notes.get("step_program")
    d_qk, d_v = attention_cost.head_sizes(view.cell.config)
    floor = spent = 0.0
    steps, by_kind = 0, {}
    for dev in trace["devices"].values():
        # whole runs of the step program inside the window, so that the
        # milliseconds a step are of whole steps
        whole = [(s, e) for s, e, n in dev["modules"]
                 if program and n.startswith(program) and lo <= s and e <= hi]
        steps += len(whole)
        for s, e, text in dev["ops"]:
            if not any(a <= s and e <= b for a, b in whole):
                continue
            kind = attention_cost.call_kind(text)
            if kind is None:
                continue
            dtype, bh, seq, _ = attention_cost.results(text)[0]
            floor += attention_cost.floor_seconds(
                kind, bh, seq, d_qk, d_v, peaks,
                itemsize={"f32": 4}.get(dtype, 2))
            spent += e - s
            calls, seconds = by_kind.get(kind, (0, 0.0))
            by_kind[kind] = (calls + 1, seconds + (e - s))
    if not spent:
        return None
    harness.say("flash_attn", steps=steps, ms_per_step=1e3 * spent / steps,
                floor_ms_per_step=1e3 * floor / steps,
                calls={k: v[0] for k, v in by_kind.items()},
                mean_ms={k: 1e3 * v[1] / v[0] for k, v in by_kind.items()})
    return 100.0 * floor / spent
