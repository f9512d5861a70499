"""Mean rows per fired batch: requests served over batches, differenced
across the window."""


def read(run):
    c = run.window.counters
    if not c.get("batches"):
        return None
    return c["served"] / c["batches"]
