"""Serving runtime: bucketed plans, the SLO scheduler, and metrics.

The bucket router must be output-transparent (same results as the base
plan, any batch size), and the scheduler must be deterministic under an
injected clock — every wait-or-fire rule is driven through virtual time.
"""

import numpy as np
import pytest
from conftest import FakeClock

from repro.engine import Engine
from repro.serving import (
    BucketedPlanSet,
    ServingMetrics,
    SparseServer,
    bucket_sizes,
    percentile,
)


@pytest.fixture
def plans(make_stack):
    return BucketedPlanSet.compile(
        make_stack(), engine=Engine(backend="jnp"), max_batch=8)


# --------------------------------------------------------------------------- #
# bucketing
# --------------------------------------------------------------------------- #

def test_bucket_sizes_powers_of_two():
    assert bucket_sizes(1) == (1,)
    assert bucket_sizes(8) == (1, 2, 4, 8)
    # non-power-of-two max still gets an exact top bucket
    assert bucket_sizes(24) == (1, 2, 4, 8, 16, 24)
    with pytest.raises(ValueError):
        bucket_sizes(0)


def test_bucket_for_routes_to_smallest_fit(plans):
    assert [plans.bucket_for(n) for n in (1, 2, 3, 4, 5, 8)] == \
        [1, 2, 4, 4, 8, 8]
    with pytest.raises(ValueError):
        plans.bucket_for(0)


def test_bucketed_outputs_match_base_plan(plans, make_stack):
    """Routing through any bucket is output-transparent, odd sizes included."""
    rng = np.random.default_rng(1)
    n_in = plans.n_in
    full = rng.standard_normal((8, n_in)).astype(np.float32)
    y_base = np.asarray(plans.base(full))
    for n in (1, 2, 3, 5, 7, 8):
        y = plans(full[:n])
        assert y.shape == (n, plans.n_out)
        np.testing.assert_array_equal(y, y_base[:n])


def test_bucketed_chunks_oversized_batches(plans):
    rng = np.random.default_rng(2)
    x = rng.standard_normal((19, plans.n_in)).astype(np.float32)
    y = plans(x)
    assert y.shape == (19, plans.n_out)
    np.testing.assert_array_equal(y[:8], plans(x[:8]))
    np.testing.assert_array_equal(y[16:], plans(x[16:19]))


def test_buckets_share_schedule_and_count_calls(plans):
    """One schedule substrate; only the jitted forward differs per bucket."""
    for b in plans.buckets:
        p = plans.plans[b]
        assert p.schedules is plans.base.schedules
        assert p.flat is plans.base.flat
        assert p.io is plans.base.io
        assert p.order is plans.base.order
    plans.warmup()
    assert all(plans.plans[b].calls == 0 for b in plans.buckets)
    rng = np.random.default_rng(3)
    plans(rng.standard_normal((3, plans.n_in)).astype(np.float32))
    plans(rng.standard_normal((4, plans.n_in)).astype(np.float32))
    plans(rng.standard_normal((1, plans.n_in)).astype(np.float32))
    assert plans.bucket_calls[4] == 2 and plans.bucket_calls[1] == 1
    assert plans.plans[4].calls == 2


def test_bucketed_rejects_bad_input(plans):
    with pytest.raises(ValueError):
        plans(np.zeros((2, plans.n_in + 1), np.float32))


# --------------------------------------------------------------------------- #
# scheduler
# --------------------------------------------------------------------------- #

def test_server_results_match_direct_plan(plans):
    rng = np.random.default_rng(4)
    xs = [rng.standard_normal(plans.n_in).astype(np.float32)
          for _ in range(11)]
    server = SparseServer(plans, slo_ms=100.0)
    rids = [server.submit(x) for x in xs]
    server.poll()
    server.drain()
    expected = plans(np.stack(xs))
    for rid, want in zip(rids, expected):
        np.testing.assert_array_equal(server.result(rid), want)
    assert server.metrics.served == 11
    assert server.queue_depth == 0


def test_admission_control_rejects_when_full(plans):
    clock = FakeClock()
    server = SparseServer(plans, max_queue=2, clock=clock)
    assert server.submit(np.zeros(plans.n_in, np.float32)) is not None
    assert server.submit(np.zeros(plans.n_in, np.float32)) is not None
    assert server.submit(np.zeros(plans.n_in, np.float32)) is None
    assert server.metrics.rejected == 1
    assert server.metrics.admitted == 2


def test_fire_on_full_batch(plans):
    clock = FakeClock()
    server = SparseServer(plans, max_batch=4, slo_ms=1e6, clock=clock)
    for _ in range(3):
        server.submit(np.zeros(plans.n_in, np.float32))
    assert not server.should_fire()    # not full, nobody waited long enough
    server.submit(np.zeros(plans.n_in, np.float32))
    assert server.should_fire()        # full batch fires immediately
    assert server.step() == 4
    assert server.metrics.bucket_hist == {4: 1}


def test_fire_on_max_wait(plans):
    clock = FakeClock()
    server = SparseServer(plans, max_batch=8, slo_ms=100.0,
                          max_wait_ms=10.0, clock=clock)
    server.submit(np.zeros(plans.n_in, np.float32))
    assert server.step() == 0          # wait: batching might still grow it
    clock.advance(0.011)               # oldest has now waited past max_wait
    assert server.should_fire()
    assert server.step() == 1
    # the 1-row tail batch went through the 1-bucket, not the full one
    assert server.metrics.bucket_hist == {1: 1}


def test_fire_before_deadline_breach(plans):
    """Deadline-aware: fire once waiting longer would miss the SLO given
    the observed batch latency."""
    clock = FakeClock()
    server = SparseServer(plans, max_batch=8, slo_ms=1000.0,
                          max_wait_ms=1000.0, clock=clock)
    server._lat_ewma[1] = 0.010        # as if 1-row batches take 10 ms
    server.submit(np.zeros(plans.n_in, np.float32), deadline_ms=15.0)
    assert not server.should_fire()    # 15 ms budget > 10 ms estimate: wait
    clock.advance(0.006)
    assert server.should_fire()        # 9 ms left <= 10 ms estimate: fire
    assert server.step() == 1


def test_deadline_miss_is_counted(plans):
    clock = FakeClock()
    server = SparseServer(plans, clock=clock)
    server.submit(np.zeros(plans.n_in, np.float32), deadline_ms=5.0)
    clock.advance(1.0)                 # way past the deadline
    server.drain()
    assert server.metrics.deadline_misses == 1
    assert server.metrics.served == 1


def test_drain_serves_everything(plans):
    clock = FakeClock()
    server = SparseServer(plans, max_batch=8, slo_ms=1e6, max_wait_ms=1e6,
                          clock=clock)
    rids = [server.submit(np.zeros(plans.n_in, np.float32))
            for _ in range(13)]
    assert server.poll() == 8          # one full batch fires, 5 wait
    assert server.drain() == 5
    assert all(server.result(r) is not None for r in rids)


def test_bucketed_call_casts_to_plan_dtype_no_retrace(make_stack):
    """A float64 client must NOT lower a second program per bucket: inputs
    are cast to the plan dtype before bucket padding.  The Python-callable
    activation runs once per layer per trace, so it counts traces."""
    traces = {"n": 0}

    def act(x):
        traces["n"] += 1
        import jax.numpy as jnp
        return jnp.maximum(x, 0)

    plans = BucketedPlanSet.compile(
        make_stack(), engine=Engine(backend="jnp", activation=act),
        max_batch=4)
    assert plans.dtype == np.float32
    plans.warmup()
    warm_traces = traces["n"]
    assert warm_traces > 0

    rng = np.random.default_rng(7)
    # float16 retraces unconditionally without the cast; float64 does too
    # whenever jax_enable_x64 is on (and costs a canonicalization otherwise)
    x64 = rng.standard_normal((3, plans.n_in))          # float64 client
    y64 = plans(x64)
    assert traces["n"] == warm_traces, "float64 input retraced a bucket"
    x16 = x64.astype(np.float16)
    plans(x16)
    assert traces["n"] == warm_traces, "float16 input retraced a bucket"
    y32 = plans(x64.astype(np.float32))
    assert traces["n"] == warm_traces
    np.testing.assert_array_equal(y64, y32)


def test_warmup_seeds_per_bucket_latency(plans):
    assert plans.warmup_s == {}
    plans.warmup()
    assert set(plans.warmup_s) == set(plans.buckets)
    assert all(t > 0 for t in plans.warmup_s.values())
    # a server built on warmed plans has a live latency estimate (and so a
    # live deadline clause) BEFORE any batch has completed
    server = SparseServer(plans, clock=FakeClock())
    est = server._estimated_batch_s(1)
    assert est > 0
    # a deadline tighter than the estimate fires immediately on submit —
    # the cold-start SLO hole this seeding closes
    server.submit(np.zeros(plans.n_in, np.float32),
                  deadline_ms=est * 1e3 / 2)
    assert server.should_fire()


def test_cold_server_without_warmup_estimates_zero(plans):
    server = SparseServer(plans, clock=FakeClock())
    assert server._estimated_batch_s(1) == 0.0


def test_result_capacity_eviction(plans):
    """Never-collected results are bounded: oldest finished results are
    evicted beyond result_capacity and counted."""
    clock = FakeClock()
    server = SparseServer(plans, max_batch=1, clock=clock,
                          result_capacity=3)
    rids = [server.submit(np.zeros(plans.n_in, np.float32))
            for _ in range(8)]
    server.drain()
    assert server.metrics.served == 8
    assert server.metrics.results_evicted == 5
    # the oldest five are gone, the newest three still collectable
    assert all(server.result(r) is None for r in rids[:5])
    assert all(server.result(r) is not None for r in rids[5:])


def test_result_ttl_eviction(plans):
    clock = FakeClock()
    server = SparseServer(plans, clock=clock, result_ttl_s=1.0)
    rid = server.submit(np.zeros(plans.n_in, np.float32))
    server.drain()
    clock.advance(2.0)                 # result now stale
    # the TTL sweep runs on the next submit (no background work needed)
    rid2 = server.submit(np.zeros(plans.n_in, np.float32))
    assert server.result(rid) is None
    assert server.metrics.results_evicted == 1
    server.drain()
    assert server.result(rid2) is not None   # fresh results unaffected


def test_queued_requests_never_evicted(plans):
    """Capacity/TTL eviction only applies to FINISHED results; queued
    requests always get served and stay collectable right after."""
    clock = FakeClock()
    server = SparseServer(plans, max_batch=8, clock=clock,
                          result_capacity=2, result_ttl_s=1.0)
    rids = [server.submit(np.zeros(plans.n_in, np.float32))
            for _ in range(6)]
    clock.advance(5.0)                 # queued far past the TTL
    server.submit(np.zeros(plans.n_in, np.float32))   # triggers TTL sweep
    assert server.queue_depth == 7
    server.drain()
    assert server.metrics.served == 7                # nothing dropped
    assert server.metrics.results_evicted == 5       # 7 done - capacity 2
    assert server.result(rids[5]) is not None        # newest survive


def test_queue_depth_convention_is_arrival_depth(plans):
    """Admitted and rejected submits record the SAME convention: the depth
    observed on arrival.  max_queue_depth is the depth attained."""
    clock = FakeClock()
    server = SparseServer(plans, max_queue=2, clock=clock)
    server.submit(np.zeros(plans.n_in, np.float32))   # sees depth 0
    server.submit(np.zeros(plans.n_in, np.float32))   # sees depth 1
    server.submit(np.zeros(plans.n_in, np.float32))   # rejected at depth 2
    assert server.metrics.queue_depth.values() == [0.0, 1.0, 2.0]
    assert server.metrics.snapshot()["max_queue_depth"] == 2


# --------------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------------- #

def test_percentile_nearest_rank():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 100) == 5.0
    assert percentile([], 50) == 0.0


def test_percentile_edge_cases():
    """Total on every input snapshot() can produce: empty and single-sample
    series, q=100 landing on max (never past the end), out-of-range q
    clamped rather than raised."""
    assert percentile([], 0) == 0.0
    assert percentile([], 100) == 0.0
    for q in (0, 50, 99, 100):
        assert percentile([7.5], q) == 7.5
    xs = [1.0, 2.0]
    assert percentile(xs, 100) == 2.0
    assert percentile(xs, 150) == 2.0     # clamps to q=100
    assert percentile(xs, -10) == 1.0     # clamps to q=0
    assert percentile(xs, 99) == 2.0      # nearest rank, not interpolation


def test_metrics_snapshot_never_raises_when_fresh():
    """A server that saw zero traffic must still snapshot/summarize."""
    m = ServingMetrics()
    s = m.snapshot()
    assert s["served"] == 0
    assert s["throughput_rps"] == 0.0
    assert s["latency_ms"]["p99"] == 0.0
    assert s["mean_batch_size"] == 0.0
    assert isinstance(m.summary(), str)
    # a single served request exercises the len-1 percentile path end-to-end
    m.record_submit(0.0, 0, admitted=True)
    m.record_batch(1.0, n=1, bucket=1, exec_s=0.25, waits_s=[0.5], misses=0)
    s = m.snapshot()
    assert s["latency_ms"]["p50"] == s["latency_ms"]["p99"] == 750.0


def test_metrics_snapshot_shape():
    m = ServingMetrics()
    m.record_submit(0.0, 1, admitted=True)
    m.record_submit(0.0, 2, admitted=True)
    m.record_batch(1.0, n=2, bucket=4, exec_s=0.5, waits_s=[0.1, 0.2],
                   misses=1)
    s = m.snapshot()
    assert s["served"] == 2 and s["batches"] == 1
    assert s["deadline_misses"] == 1
    assert s["padding_fraction"] == pytest.approx(0.5)
    assert s["latency_ms"]["p50"] <= s["latency_ms"]["p99"]
    assert s["bucket_hist"] == {"4": 1}
    assert s["throughput_rps"] == pytest.approx(2.0)
    assert "p50" in m.summary() or "latency" in m.summary()


# --------------------------------------------------------------------------- #
# the serving CLI's exit code
# --------------------------------------------------------------------------- #

def _serve_cli(monkeypatch, *extra):
    from repro.launch import serve

    # the CLI turns on the persistent compile cache; not in a test process
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", [
        "serve", "--sparse-ffnn", "--backend", "jnp", "--requests", "6",
        "--batch", "4", "--reorder-iters", "10", "--block", "32",
        "--ffnn-sizes", "64", "128", "64", *extra])
    return serve.main()


@pytest.mark.parametrize("mode", [(), ("--async",)], ids=["step", "async"])
def test_serve_cli_exits_zero_when_every_request_is_served(monkeypatch, mode):
    assert _serve_cli(monkeypatch, *mode) == 0


@pytest.mark.parametrize("mode", [(), ("--async",)], ids=["step", "async"])
def test_serve_cli_exits_nonzero_on_failed_batches(monkeypatch, capsys, mode):
    def broken(self, x):
        raise RuntimeError("injected batch failure")

    monkeypatch.setattr(BucketedPlanSet, "__call__", broken)
    assert _serve_cli(monkeypatch, *mode) == 1
    assert "FAILED" in capsys.readouterr().err
