"""The megakernel's share of its roofline in the traced window: the sum
over its calls of the least time each could take on this chip (the larger
of its operations over peak FLOP/s and its bytes over peak bytes/s, from
``counts.py``), over the sum of their device durations."""

from bench.peaks import peak


def read(run):
    calls = run.trace.kernel_calls if run.trace is not None else []
    if not calls:
        return None
    p = peak(run.device_kind)
    bound = sum(run.counts.bound_s(rows, p) for rows, _ in calls)
    return 100.0 * bound / sum(s for _, s in calls)
