"""Device time of one megakernel grid step in the traced window: the
kernel calls' device seconds over the grid steps the program counted as
it dispatched them (``megakernel.steps`` of ``repro.obs.counter_totals``,
which counts only while the profiler records)."""


def read(run):
    calls = run.trace.kernel_calls if run.trace is not None else []
    if not calls:
        return None
    try:
        from repro.obs import counter_totals
    except ImportError:     # a program that counts no grid steps
        return None
    steps = counter_totals().get("megakernel.steps", 0)
    return 1e9 * sum(s for _, s in calls) / steps if steps else None
