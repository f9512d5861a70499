"""Wall time of ``BucketedPlanSet.compile``: the engine's schedule and
lowering, and the fan-out over buckets."""


def read(run):
    return run.setup_phases["engine_compile"]
