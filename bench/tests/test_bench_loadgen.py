"""The traffic generator's schedules, from a seed."""

import time

import numpy as np
import pytest

from bench import loadgen


def test_poisson_due_same_work_from_every_seed():
    a = loadgen.poisson_due(5000.0, 2.0, seed=2**31 + 7)
    b = loadgen.poisson_due(5000.0, 2.0, seed=3)
    assert len(a) == len(b) == 10000
    assert a[-1] == pytest.approx(2.0) and b[-1] == pytest.approx(2.0)
    assert np.all(np.diff(a) > 0) and a[0] > 0
    ga, gb = np.diff(np.r_[0.0, a]), np.diff(np.r_[0.0, b])
    # the same set of gaps, in another order
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=1e-9, atol=1e-12)
    assert not np.allclose(ga, gb)
    # exponential: mean 1/rate, coefficient of variation about 1
    assert ga.mean() == pytest.approx(1 / 5000.0, rel=1e-9)
    assert ga.std() / ga.mean() == pytest.approx(1.0, abs=0.05)


def test_poisson_due_repeats_for_a_seed():
    np.testing.assert_array_equal(loadgen.poisson_due(300.0, 1.5, 11),
                                  loadgen.poisson_due(300.0, 1.5, 11))


def test_reservoir_keeps_a_uniform_sample():
    r = loadgen.Reservoir(100, seed=5, width=1)
    for i in range(10000):
        r.offer(i, float(i))
    idx, out = r.taken()
    assert len(idx) == 100 and len(set(idx.tolist())) == 100
    np.testing.assert_array_equal(out[:, 0], idx.astype(np.float32))
    assert 3000 < idx.mean() < 7000


class FakePlans:
    """Records the rows of each call; answers with the input's row sums."""

    n_in = n_out = 8
    max_batch = 4

    def __init__(self):
        self.calls = []

    def warmup(self):
        return self

    def __call__(self, x):
        self.calls.append(len(x))
        return np.repeat(np.asarray(x).sum(1, keepdims=True), 8, axis=1)


class FakeServer:
    """FIFO server that answers a request once ``wait`` asks for it and
    counts how many are in flight at most."""

    def __init__(self, plans, slo_ms, max_batch, result_capacity):
        self.plans, self.pending, self.peak = plans, {}, 0
        self.next = 0
        self.metrics = type("M", (), {})()
        for k in ("served", "batches", "batch_failures", "failed_requests",
                  "degraded_batches", "rejected"):
            setattr(self.metrics, k, 0)
        s = type("S", (), {"total": 0.0, "count": 0})
        self.metrics.form_wait_s = s()
        self.metrics.exec_s = s()

    def start(self):
        return self

    def submit(self, x):
        rid = self.next
        self.next += 1
        self.pending[rid] = x
        self.peak = max(self.peak, len(self.pending))
        return rid

    def status(self, rid):
        return "pending" if rid in self.pending else "unknown"

    def wait(self, rid, timeout=None):
        x = self.pending.pop(rid)
        return self.plans(x[None])[0]

    def shutdown(self, drain=True):
        pass


def test_closed_loop_keeps_its_outstanding_count(monkeypatch):
    monkeypatch.setattr(loadgen, "SparseServer", FakeServer)
    system = type("Sys", (), {"plans": FakePlans()})()
    traffic = {"outstanding": 6, "pool_rows": 32, "warmup_s": 0.01}
    load = loadgen.ClosedLoop(system, {"slo_ms": 50.0, "max_batch": 4},
                                traffic, seed=1)
    w = load.run(0.05)
    assert load.server.peak == 6
    assert w.failed == 0 and w.rows > 0 and w.attempted >= w.rows
    # every sampled answer is its own pool row's answer
    np.testing.assert_allclose(w.sample_out[:, 0],
                               load.pool[w.sample_idx].sum(1), rtol=1e-5)


def test_offline_calls_carry_rows_per_call():
    plans = FakePlans()
    system = type("Sys", (), {"plans": plans})()
    load = loadgen.Offline(system, {}, {"rows_per_call": 40,
                                          "pool_calls": 2}, seed=9)
    w = load.run(0.05)
    assert set(plans.calls) == {40} and len(plans.calls) >= 2
    assert w.rows == 40 * (len(plans.calls) - 1)    # one warm-up call
    assert w.attempted == w.rows and w.failed == 0
    np.testing.assert_allclose(w.sample_out[:, 0],
                               load.pool[w.sample_idx].sum(1), rtol=1e-5)


class OutOfOrderServer:
    """Answers request ``rid`` at ``done_at[rid]`` seconds after it was
    made: a later batch can finish before an earlier one."""

    def __init__(self, done_at):
        t0 = time.perf_counter()
        self.due = {rid: t0 + d for rid, d in enumerate(done_at)}

    @property
    def metrics(self):
        """Each request is a batch of its own."""
        now = time.perf_counter()
        done = sum(now >= t for t in self.due.values())
        return type("M", (), {"batches": done + self.taken,
                              "batch_failures": 0})

    taken = 0

    def status(self, rid):
        if rid not in self.due:
            return "unknown"
        return "done" if time.perf_counter() >= self.due[rid] else "pending"

    def wait(self, rid, timeout=None):
        left = self.due[rid] - time.perf_counter()
        time.sleep(max(0.0, min(left, timeout)))
        if self.status(rid) != "done":
            return None
        del self.due[rid]
        self.taken += 1
        return np.full(2, float(rid))


def test_an_answer_that_finishes_first_is_taken_first():
    """The oldest request is slow, the newer one fast: the newer answer is
    taken when it is done, not when the oldest is."""
    server = OutOfOrderServer([0.12, 0.01])
    out = loadgen.Outstanding(server, loadgen.no_span)
    out.add(0, 0)
    out.add(1, 1)
    t0 = time.perf_counter()
    taken = {}
    while out:
        out.take(lambda j, y, t: taken.setdefault(j, (y[0], t - t0)))
    assert taken[1][0] == 1.0 and taken[1][1] < 0.05
    assert taken[0][0] == 0.0 and taken[0][1] >= 0.12
