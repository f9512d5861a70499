"""Share of the traced window the server spent forming, stacking,
executing and completing batches: the profiled totals of the program's
``batch.form``, ``batch.stack``, ``batch.execute`` and ``batch.complete``
spans over the window.  Inline execution (the server's default) runs all
four on the one scheduler thread."""

STAGES = ("batch.form", "batch.stack", "batch.execute", "batch.complete")


def read(run):
    try:
        from repro.obs import span_totals
    except ImportError:     # a program whose spans keep no profiled totals
        return None
    totals = span_totals()
    if not any(totals.get(k, (0, 0.0))[0] for k in STAGES):
        return None
    busy = sum(totals.get(k, (0, 0.0))[1] for k in STAGES)
    return 100.0 * busy / run.window.seconds
