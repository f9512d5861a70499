"""Mean host time of one bucket program call in the traced window: the
program's ``plans.dispatch`` span (cast, padding, the host-to-device copy
and the enqueue), its profiled total over its count."""


def read(run):
    try:
        from repro.obs import span_totals
    except ImportError:     # a program whose spans keep no profiled totals
        return None
    n, s = span_totals().get("plans.dispatch", (0, 0.0))
    return s / n * 1e3 if n else None
