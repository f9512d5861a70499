"""Mean time the plan set takes to join a chunked call's outputs in the
traced window: the program's ``plans.concat`` span, its profiled total
over its count."""


def read(run):
    try:
        from repro.obs import span_totals
    except ImportError:     # a program whose spans keep no profiled totals
        return None
    n, s = span_totals().get("plans.concat", (0, 0.0))
    return s / n * 1e3 if n else None
