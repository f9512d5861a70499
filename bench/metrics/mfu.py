"""The whole forward's share of the chip's peak: rows answered per second
in the window times the useful operations of one row (2 * nonzero blocks
* block^2), over the published bf16 peak."""

from bench.peaks import peak


def read(run):
    w = run.window
    if not w.rows:
        return None
    rate = w.rows / w.seconds
    return 100.0 * rate * run.counts.flops_per_row() / peak(
        run.device_kind)["flops_per_s"]
