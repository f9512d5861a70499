"""The readers of the program's profiled span totals: the plan set's
dispatch, fetch and concatenation, and the scheduler's busy share."""

import types

import pytest

import repro.obs
from bench.spec import load_reader

TOTALS = {
    "plans.dispatch": (64, 0.032),
    "plans.fetch": (64, 0.128),
    "plans.concat": (2, 0.1),
    "batch.form": (40, 0.5),
    "batch.stack": (20, 0.25),
    "batch.execute": (20, 2.0),
    "batch.complete": (20, 0.25),
}
RUN = types.SimpleNamespace(window=types.SimpleNamespace(seconds=10.0))


@pytest.fixture
def totals(monkeypatch):
    """Set what ``repro.obs.span_totals`` reads."""
    def put(values):
        monkeypatch.setattr(repro.obs, "span_totals", lambda: dict(values))
    return put


@pytest.mark.parametrize("metric,value", [
    ("plan_dispatch_mean_ms.steady", 0.5),
    ("plan_dispatch_mean_ms.offline", 0.5),
    ("plan_fetch_mean_ms.flood", 2.0),
    ("plan_concat_ms.offline", 50.0),
    ("sched_busy_pct.flood", 30.0),
])
def test_reader_value_on_set_totals(totals, metric, value):
    totals(TOTALS)
    assert load_reader(metric)(RUN) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "plan_dispatch_mean_ms.steady", "plan_fetch_mean_ms.steady",
    "plan_concat_ms.offline", "sched_busy_pct.flood"])
@pytest.mark.parametrize("values", [
    {}, {name: (0, 0.0) for name in TOTALS}], ids=["absent", "zero"])
def test_reader_reads_nothing_on_zero_counts(totals, metric, values):
    totals(values)
    assert load_reader(metric)(RUN) is None


@pytest.mark.parametrize("metric", [
    "plan_dispatch_mean_ms.flood", "plan_fetch_mean_ms.offline",
    "plan_concat_ms.offline", "sched_busy_pct.flood"])
def test_reader_reads_nothing_from_a_program_without_totals(monkeypatch,
                                                            metric):
    """A program older than the profiled totals: nothing, and no raise."""
    monkeypatch.delattr(repro.obs, "span_totals")
    assert load_reader(metric)(RUN) is None
