"""Mean time of one bucket program's answer in the traced window: the
program's ``plans.fetch`` span (the wait for the device and the
device-to-host copy), its profiled total over its count."""


def read(run):
    try:
        from repro.obs import span_totals
    except ImportError:     # a program whose spans keep no profiled totals
        return None
    n, s = span_totals().get("plans.fetch", (0, 0.0))
    return s / n * 1e3 if n else None
