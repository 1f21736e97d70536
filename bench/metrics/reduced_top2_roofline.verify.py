"""Share of the roofline reached by the ``reduced_top2`` kernel, in %:
the least time its calls need (operations and bytes from each call's
shapes, over the peaks of the device kind) over their device time in the
trace.  Nothing is returned when the trace holds no call of the kernel or
a call whose shapes cannot be read."""

from bench import peaks


def read(run):
    t = run.trace
    if not t:
        return None
    calls = t["kernels"].get("reduced_top2", {}).get("calls", [])
    least = spent = 0.0
    for seconds, name, stats in calls:
        # the op's name is its HLO text, operand shapes included
        shape = next((s for s in map(peaks.square_operand,
                                     [name] + [str(v) for v in stats.values()])
                      if s), None)
        if shape is None:
            return None
        ops, nbytes = peaks.reduced_top2_cost(*shape)
        least += peaks.least_time(ops, nbytes, run.device_kind)[0]
        spent += seconds
    return 100.0 * least / spent if spent > 0 else None
