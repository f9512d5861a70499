"""Wall time of warming the shapes the cell's traffic uses:
``BucketedPlanSet.warmup()`` for the served mixes, one call of the
offline shape otherwise."""


def read(run):
    return run.setup_phases["bucket_warmup"]
