"""Observability: tracer, bounded series, I/O telemetry, Prometheus export.

Covers the PR-8 acceptance scenarios end to end:

  * a single request is followable through the exported trace
    (submit -> queue -> batch.execute -> done) with bucket/model/I/O
    attributes on the spans;
  * the chaos lifecycle (injected failure -> breaker trip -> degraded
    serving -> half-open -> recovery) appears in span order, and the
    Chrome-trace export is structurally valid (monotonic ``ts``, complete
    ``X`` events);
  * ``BoundedSeries`` answers percentiles exactly below its cap (bit-for-bit
    with the legacy list implementation) and within the documented ~12%
    relative error after collapsing, at fixed memory;
  * the Prometheus endpoint exposes the per-bucket dynamic-vs-static
    block-read gauges for a gated model over real HTTP.
"""

import contextlib
import glob
import json
import math
import threading
import urllib.error
import urllib.request
import warnings

import jax
import numpy as np
import pytest
from conftest import FakeClock

from repro.engine import Engine
from repro.obs import (
    BoundedSeries,
    IOTelemetry,
    MetricsServer,
    Tracer,
    count,
    counter_totals,
    plan_io_attrs,
    render_prometheus,
    reset_span_totals,
    span_totals,
)
from repro.obs.trace import _NULL_SPAN, NULL_TRACER
from repro.serving import (
    BucketedPlanSet,
    CircuitBreaker,
    FaultInjector,
    ModelRouter,
    PlanStore,
    RetryPolicy,
    SparseServer,
)
from repro.serving.metrics import percentile


# --------------------------------------------------------------------------- #
# Tracer
# --------------------------------------------------------------------------- #

def test_tracer_span_event_and_attrs():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("work", k=1) as sp:
        clk.advance(0.5)
        sp["out"] = 2
    tr.event("tick", n=3)
    spans = tr.spans()
    assert [s.name for s in spans] == ["work", "tick"]
    assert spans[0].phase == "X"
    assert spans[0].dur == pytest.approx(0.5)
    assert spans[0].attrs == {"k": 1, "out": 2}
    assert spans[1].phase == "i" and spans[1].attrs == {"n": 3}


def test_tracer_ring_bound_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.event("e", i=i)
    assert tr.recorded == 10
    assert tr.dropped == 6
    assert [s.attrs["i"] for s in tr.spans()] == [6, 7, 8, 9]
    snap = tr.snapshot()
    assert snap["buffered"] == 4 and snap["dropped"] == 6


def test_disabled_tracer_is_inert():
    tr = Tracer(enabled=False)
    with tr.span("x", a=1) as sp:
        sp["b"] = 2          # must be a silent no-op, not an AttributeError
    tr.event("y")
    tr.span_at("z", 0.0, 1.0)
    assert tr.spans() == [] and tr.recorded == 0
    assert NULL_TRACER.spans() == [] and not NULL_TRACER.enabled


def test_span_ctx_records_exception_type():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("no")
    (s,) = tr.spans()
    assert s.attrs["error"] == "ValueError"


@pytest.mark.stress
def test_tracer_thread_safety():
    tr = Tracer(capacity=100_000)

    def worker(k):
        for i in range(500):
            tr.event("e", k=k, i=i)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.recorded == 8 * 500
    assert len(tr.spans()) == 8 * 500 and tr.dropped == 0


def test_chrome_export_is_valid(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("a", x=1):
        clk.advance(0.1)
    tr.event("b")
    clk.advance(0.1)
    tr.span_at("c", 0.05, 0.15)     # retroactive: recorded out of ts order
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as fh:
        doc = json.load(fh)
    evs = doc["traceEvents"]
    assert len(evs) == 3
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts), "export must sort retroactive spans by ts"
    for e in evs:
        assert set(e) >= {"name", "cat", "ph", "ts", "pid", "tid", "args"}
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0.0
        else:
            assert e["ph"] == "i" and e["s"] == "t"


def test_jsonl_export_round_trips(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("a", x=1):
        clk.advance(0.25)
    path = tr.export(str(tmp_path / "trace.jsonl"))
    assert path.endswith(".jsonl")
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 1
    assert lines[0]["name"] == "a" and lines[0]["dur"] == pytest.approx(0.25)
    assert lines[0]["attrs"] == {"x": 1}


# --------------------------------------------------------------------------- #
# BoundedSeries
# --------------------------------------------------------------------------- #

def test_bounded_series_exact_prefix_matches_legacy_percentile():
    rng = np.random.default_rng(0)
    xs = [float(v) for v in rng.exponential(0.05, size=1000)]
    s = BoundedSeries()
    s.extend(xs)
    assert s.exact and s.values() == xs
    for q in (0, 10, 50, 90, 99, 100):
        assert s.percentile(q) == percentile(xs, q)
    assert s.mean() == pytest.approx(sum(xs) / len(xs))


def test_bounded_series_post_cap_error_bound_and_fixed_memory():
    rng = np.random.default_rng(1)
    xs = [float(v) for v in rng.exponential(0.05, size=20_000)]
    s = BoundedSeries(exact_cap=1024)
    s.extend(xs)
    assert not s.exact and s.values() is None
    assert s.count == 20_000
    assert s.vmin == min(xs) and s.vmax == max(xs)
    assert s.total == pytest.approx(sum(xs))
    bound = math.sqrt(s.growth) - 1       # documented relative error
    for q in (50, 90, 99):
        want = percentile(xs, q)
        got = s.percentile(q)
        assert abs(got - want) / want <= bound + 1e-9, (q, got, want)


def test_bounded_series_extremes_stay_exact_after_collapse():
    s = BoundedSeries(exact_cap=4)
    s.extend([3.0, 1.0, 9.0, 2.0, 5.0, 0.5])
    assert not s.exact
    assert s.percentile(0) >= s.vmin and s.percentile(100) <= s.vmax
    assert s.vmin == 0.5 and s.vmax == 9.0


def test_bounded_series_buckets_are_cumulative():
    rng = np.random.default_rng(2)
    s = BoundedSeries(exact_cap=8)
    s.extend(float(v) for v in rng.exponential(0.01, size=500))
    pairs = list(s.buckets())
    edges = [e for e, _ in pairs]
    counts = [c for _, c in pairs]
    assert counts == sorted(counts) and counts[-1] == s.count
    assert edges == sorted(edges) and math.isinf(edges[-1])


def test_bounded_series_empty_and_single():
    s = BoundedSeries()
    assert len(s) == 0 and not s and s.percentile(50) == 0.0
    s.add(0.75)
    for q in (0, 50, 100):
        assert s.percentile(q) == 0.75
    d = s.to_dict()
    assert d["count"] == 1 and d["min"] == d["max"] == 0.75


# --------------------------------------------------------------------------- #
# I/O telemetry
# --------------------------------------------------------------------------- #

def test_plan_io_attrs_static(make_stack):
    plan = Engine(backend="jnp", reorder_iters=20).compile(make_stack())
    attrs = plan.trace_attrs()
    assert attrs["backend"] == "jnp"
    assert attrs["io_tile_reads"] >= 1
    assert attrs["io_tile_total"] == \
        attrs["io_tile_reads"] + attrs["io_tile_writes"]
    assert attrs["nnz_blocks"] > 0
    assert isinstance(attrs["io_within_bounds"], bool)
    # a chain has no gate/up pairs; packing patched what no block reaches
    assert attrs["glu_pairs"] == 0
    assert attrs["patched_steps"] == sum(s.patched for s in plan.schedules)
    # defensive on non-plan objects: empty dict, never a raise
    assert plan_io_attrs(object()) == {}


def test_io_telemetry_aggregates_dynamic_reports(make_stack):
    plan = Engine(backend="jnp", gate=True,
                  reorder_iters=20).compile(make_stack())
    telem = IOTelemetry(model="m")
    telem.observe_plan(4, plan)
    # an all-zero batch gates every block: dynamic reads must undercut the
    # static schedule
    rep = plan.measure_dynamic(np.zeros((4, plan.n_in), np.float32))
    telem.observe_dynamic(4, rep)
    snap = telem.snapshot()
    assert snap["model"] == "m" and snap["batches_measured"] == 1
    b = snap["buckets"][4]
    assert b["static_blocks"] > 0 and b["weight_bytes"] > 0
    assert b["dynamic_blocks"] < b["static_scheduled"]
    assert 0.0 <= b["read_fraction"] <= 1.0
    assert set(b["occupancy_hist"]) == {"dead", "lt25", "lt50",
                                        "lt75", "le100"}
    assert snap["dynamic_blocks"] == b["dynamic_blocks"]


# --------------------------------------------------------------------------- #
# serving integration: one request, end to end
# --------------------------------------------------------------------------- #

def test_single_request_followable_in_trace(make_stack):
    clock = FakeClock()
    tr = Tracer(clock=clock)
    plans = BucketedPlanSet.compile(make_stack(),
                                    engine=Engine(backend="jnp"), max_batch=8)
    srv = SparseServer(plans, clock=clock, tracer=tr, name="m0")
    rid = srv.submit(np.ones(plans.n_in, np.float32))
    clock.advance(0.01)
    srv.drain()
    assert srv.result(rid) is not None

    spans = srv.tracer.spans()
    names = [s.name for s in spans]
    i_sub = names.index("request.submit")
    i_q = names.index("request.queue")
    i_ex = names.index("batch.execute")
    i_done = names.index("request.done")
    assert i_sub < i_ex < i_done

    sub = spans[i_sub]
    assert sub.attrs["rid"] == rid and sub.attrs["admitted"] is True
    q = spans[i_q]
    assert q.attrs["rid"] == rid and q.attrs["bucket"] == 1
    ex = spans[i_ex]
    assert ex.attrs["model"] == "m0" and ex.attrs["bucket"] == 1
    assert ex.attrs["n"] == 1 and ex.attrs["degraded"] is False
    assert "io_tile_reads" in ex.attrs          # plan I/O rides on the span
    # the queue span closes exactly where the execute span opens
    assert q.t1 == ex.t0
    done = spans[i_done]
    assert done.attrs["rid"] == rid and done.attrs["ok"] is True
    assert done.attrs["miss"] is False


def test_rejected_submit_traced(make_stack):
    clock = FakeClock()
    tr = Tracer(clock=clock)
    plans = BucketedPlanSet.compile(make_stack(),
                                    engine=Engine(backend="jnp"), max_batch=8)
    srv = SparseServer(plans, clock=clock, tracer=tr, max_queue=1)
    srv.submit(np.zeros(plans.n_in, np.float32))
    assert srv.submit(np.zeros(plans.n_in, np.float32)) is None
    subs = [s for s in tr.spans() if s.name == "request.submit"]
    assert [s.attrs["admitted"] for s in subs] == [True, False]


def test_swap_emits_plan_swap_span(make_stack):
    clock = FakeClock()
    tr = Tracer(clock=clock)
    engine = Engine(backend="jnp", reorder_iters=20, tracer=tr)
    plans = BucketedPlanSet.compile(make_stack(), engine=engine, max_batch=8)
    srv = SparseServer(plans, clock=clock, tracer=tr, engine=engine)
    srv.swap(make_stack(seed=1))
    swaps = [s for s in tr.spans() if s.name == "plan.swap"]
    assert len(swaps) == 1
    assert swaps[0].attrs["cache_hit"] is False
    # the engine shares the tracer, so the swap's recompile phases land in
    # the same buffer
    assert any(s.name == "compile.theorem1" for s in tr.spans())


def test_tracing_disabled_by_default_and_keeps_serving(make_stack):
    plans = BucketedPlanSet.compile(make_stack(),
                                    engine=Engine(backend="jnp"), max_batch=8)
    srv = SparseServer(plans, clock=FakeClock())
    assert srv.tracer is NULL_TRACER
    rid = srv.submit(np.zeros(plans.n_in, np.float32))
    srv.drain()
    assert srv.result(rid) is not None
    assert NULL_TRACER.spans() == []
    assert "tracer" not in srv.snapshot()


# --------------------------------------------------------------------------- #
# chaos scenario: the whole breaker lifecycle in one exported trace
# --------------------------------------------------------------------------- #

def test_chaos_breaker_lifecycle_trace(make_stack, tmp_path):
    clock = FakeClock()
    tr = Tracer(clock=clock)
    plans = BucketedPlanSet.compile(make_stack(),
                                    engine=Engine(backend="jnp"),
                                    max_batch=8, safe_twin=True)
    inj = FaultInjector()
    srv = SparseServer(plans, slo_ms=50.0, clock=clock, tracer=tr, name="m0",
                       retry=RetryPolicy(max_retries=0, backoff_s=0.0),
                       breaker=CircuitBreaker(threshold=2, cooldown_s=5.0),
                       fault_injector=inj)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal(plans.n_in).astype(np.float32)
          for _ in range(8)]

    # two consecutive poisoned batches trip the breaker
    inj.inject("server.run_batch",
               error=RuntimeError("poisoned kernel"), times=2)
    srv.submit(xs[0])
    srv.drain()
    clock.advance(0.01)
    srv.submit(xs[1])
    srv.drain()
    assert srv.breaker.state == "open"

    # degraded traffic on the safe twin
    clock.advance(0.01)
    rid = srv.submit(xs[2])
    srv.drain()
    assert srv.result(rid) is not None

    # cool-down elapses: half-open probe on the fast plan succeeds -> reset
    clock.advance(6.0)
    rid = srv.submit(xs[3])
    srv.drain()
    assert srv.result(rid) is not None
    assert srv.breaker.state == "closed"

    spans = tr.spans()

    def first(pred):
        for i, s in enumerate(spans):
            if pred(s):
                return i
        raise AssertionError("span not found")

    fails = [i for i, s in enumerate(spans)
             if s.name == "batch.execute" and "error" in s.attrs]
    assert len(fails) == 2
    assert all(spans[i].attrs["error"] == "RuntimeError" for i in fails)
    i_trip = first(lambda s: s.name == "breaker.tripped")
    i_deg = first(lambda s: s.name == "batch.execute"
                  and s.attrs.get("degraded") and "error" not in s.attrs)
    i_half = first(lambda s: s.name == "breaker.half_open")
    i_reset = first(lambda s: s.name == "breaker.reset")
    assert fails[1] < i_trip < i_deg < i_half < i_reset
    assert spans[i_trip].attrs["state"] == "open"
    assert spans[i_trip].attrs["model"] == "m0"
    assert spans[i_reset].attrs["state"] == "closed"
    # failed requests get done events with ok=False
    dones = [s for s in spans if s.name == "request.done"]
    assert [s.attrs["ok"] for s in dones] == [False, False, True, True]

    # the exported Chrome trace of the whole scenario is structurally valid
    doc = json.load(open(tr.export(str(tmp_path / "chaos.json"))))
    evs = doc["traceEvents"]
    assert len(evs) == len(spans)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    for e in evs:
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0.0


@pytest.mark.stress
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_watchdog_restart_traced(make_stack):
    plans = BucketedPlanSet.compile(make_stack(),
                                    engine=Engine(backend="jnp"), max_batch=8)
    inj = FaultInjector()
    tr = Tracer()
    srv = SparseServer(plans, slo_ms=20.0, tracer=tr, fault_injector=inj,
                       watchdog_s=0.2)
    inj.inject("server.scheduler", error=RuntimeError("sched dies"), times=1)
    srv.start()                                # dies on its first iteration
    try:
        rid = srv.submit(np.zeros(plans.n_in, np.float32))
        assert srv.wait(rid, timeout=10.0) is not None
        assert srv.metrics.watchdog_restarts >= 1
    finally:
        srv.shutdown()
    restarts = [s for s in tr.spans() if s.name == "watchdog.restart"]
    assert restarts and restarts[0].attrs["model"] == "default"


# --------------------------------------------------------------------------- #
# engine + plan store compile-phase spans
# --------------------------------------------------------------------------- #

def test_engine_compile_phases_traced(make_stack):
    tr = Tracer()
    Engine(backend="jnp", reorder=True, reorder_iters=20,
           tracer=tr).compile(make_stack())
    names = [s.name for s in tr.spans()]
    for phase in ("compile.theorem1", "compile.reorder", "compile.pack",
                  "compile.lower", "compile.io_report"):
        assert phase in names, phase
    # the annealer span knows how many connections it ordered
    th = next(s for s in tr.spans() if s.name == "compile.theorem1")
    assert th.attrs["connections"] > 0


def test_plan_store_traces_miss_then_hit(make_stack, tmp_path):
    tr = Tracer()
    store = PlanStore(str(tmp_path / "plans"), tracer=tr)
    engine = Engine(backend="jnp", reorder_iters=20)
    net = make_stack()
    _, hit0 = store.get_or_compile(engine, net)
    _, hit1 = store.get_or_compile(engine, net)
    assert (hit0, hit1) == (False, True)
    loads = [s for s in tr.spans() if s.name == "store.load"]
    assert [s.attrs["hit"] for s in loads] == [False, True]
    assert sum(s.name == "store.compile" for s in tr.spans()) == 1


def test_bucket_fanout_and_warmup_traced(make_stack):
    tr = Tracer()
    engine = Engine(backend="jnp", tracer=tr)
    plans = BucketedPlanSet.compile(make_stack(), engine=engine, max_batch=4)
    plans.warmup()
    spans = tr.spans()
    fan = next(s for s in spans if s.name == "bucket.fanout")
    assert fan.attrs["buckets"] == len(plans.buckets)
    warms = [s for s in spans if s.name == "bucket.warmup"]
    assert sorted(s.attrs["bucket"] for s in warms) == list(plans.buckets)
    assert all(s.attrs["warmup_s"] >= 0.0 for s in warms)


# --------------------------------------------------------------------------- #
# profiler capture: scoped spans on the profiler's clock, and their totals
# --------------------------------------------------------------------------- #

@contextlib.contextmanager
def _profiled(tmp_path):
    """A ``jax.profiler`` session around the block; yields a list that is
    filled, once the session stops, with the ``/host:CPU`` plane's events
    as ``(name, start_ns, end_ns, args)``."""
    reset_span_totals()
    events = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield events
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    with warnings.catch_warnings():
        # the reader's stats type warns when its values are read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if plane.name == "/host:CPU":
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats))
                           for line in plane.lines for e in line.events]


def _serve_and_chunk(plans, clock, batches=3):
    """Step-driven serving of ``batches`` batches of 3 rows, then one
    offline call of three chunks (8 + 8 + 4 rows)."""
    srv = SparseServer(plans, clock=clock)
    rng = np.random.default_rng(0)
    for _ in range(batches):
        rids = [srv.submit(rng.standard_normal(plans.n_in).astype(np.float32))
                for _ in range(3)]
        clock.advance(0.01)
        srv.drain()
        assert all(srv.result(r) is not None for r in rids)
    y = plans(rng.standard_normal((20, plans.n_in)).astype(np.float32))
    assert y.shape == (20, plans.n_out)


def test_profiler_capture_totals_and_host_plane(make_stack, tmp_path):
    plans = BucketedPlanSet.compile(make_stack(),
                                    engine=Engine(backend="jnp"), max_batch=8)
    plans.warmup()
    with _profiled(tmp_path) as events:
        _serve_and_chunk(plans, FakeClock())
    totals = span_totals()
    # one execute per batch; one dispatch and fetch per bucket call (three
    # batches, three chunks); one concat, for the chunked call
    assert totals["batch.execute"][0] == 3
    assert totals["batch.stack"][0] == totals["batch.complete"][0] == 3
    assert totals["batch.form"][0] >= 3
    assert totals["plans.dispatch"][0] == totals["plans.fetch"][0] == 6
    assert totals["plans.concat"][0] == 1
    assert totals["plans.call"][0] == 4
    assert all(s >= 0.0 for _, s in totals.values())
    # request-following spans and events stay in the ring
    assert not {"request.queue", "request.submit", "request.done"} & \
        set(totals)

    names = {e[0] for e in events}
    assert set(totals) <= names
    for name, _, _, args in events:
        if name in totals:
            assert set(args) <= {"bucket", "rows"}, (name, args)
    fetches = [e for e in events if e[0] == "plans.fetch"]
    executes = [e for e in events if e[0] == "batch.execute"]
    assert len(executes) == 3
    for _, t0, t1, args in executes:
        assert any(t0 <= f0 and f1 <= t1 for _, f0, f1, _ in fetches)
        assert args["bucket"] == 4


def test_profiler_capture_async_scheduler(make_stack, tmp_path):
    plans = BucketedPlanSet.compile(make_stack(),
                                    engine=Engine(backend="jnp"), max_batch=8)
    plans.warmup()
    srv = SparseServer(plans, slo_ms=20.0)
    with _profiled(tmp_path):
        srv.start()
        try:
            rid = srv.submit(np.zeros(plans.n_in, np.float32))
            assert srv.wait(rid, timeout=10.0) is not None
        finally:
            srv.shutdown()
    totals = span_totals()
    assert totals["sched.wait"][0] >= 1
    assert totals["batch.execute"][0] >= 1


def test_ring_and_profiler_share_one_span(tmp_path):
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with _profiled(tmp_path) as events:
        with tr.span("plans.dispatch", bucket=2, rows=1, model="m") as sp:
            clock.advance(0.5)
            sp["k"] = 1
    (ring,) = tr.spans()
    assert ring.attrs == {"bucket": 2, "rows": 1, "model": "m", "k": 1}
    assert ring.dur == pytest.approx(0.5)
    assert span_totals()["plans.dispatch"][0] == 1
    (prof,) = [e for e in events if e[0] == "plans.dispatch"]
    assert prof[3] == {"bucket": 2, "rows": 1}


def test_counters_count_only_while_a_profiler_records(tmp_path):
    reset_span_totals()
    count("megakernel.steps", 5)
    assert counter_totals() == {}
    with _profiled(tmp_path):
        count("megakernel.steps", 5)
        count("megakernel.steps", 2)
        count("megakernel.glu_steps", 4)
    count("megakernel.steps", 100)          # the session has stopped
    assert counter_totals() == {"megakernel.steps": 7,
                                "megakernel.glu_steps": 4}
    reset_span_totals()                     # resets with the span totals
    assert counter_totals() == {}


@pytest.mark.parametrize("glu", [False, True], ids=["chain", "glu"])
def test_plan_set_counts_megakernel_grid_steps(make_stack, tmp_path, glu):
    """Each kernel call's grid steps at dispatch, while a profiler records:
    one offline call of three chunks; a gated block's pairs besides."""
    if glu:
        from repro.sparse import prune_glu
        rng = np.random.default_rng(2)
        net = prune_glu(
            [0.1 * rng.standard_normal(s).astype(np.float32)
             for s in ((128, 256), (128, 256), (256, 128))],
            [np.zeros(n, np.float32) for n in (256, 256, 128)], 0.5, 128)
    else:
        net = make_stack()
    plans = BucketedPlanSet.compile(
        net, engine=Engine(backend="interpret", activation="silu"),
        max_batch=8)
    flat = plans.base.flat
    x = np.ones((20, plans.n_in), np.float32)
    plans(x)                                  # traced and run outside
    with _profiled(tmp_path):
        plans(x)
    steps = counter_totals()
    assert steps["megakernel.steps"] == 3 * flat.nnz
    if glu:
        assert flat.glu_pairs > 0
        assert steps["megakernel.glu_steps"] == 3 * 2 * flat.glu_pairs
    else:
        assert "megakernel.glu_steps" not in steps
    jnp_plans = BucketedPlanSet.compile(net, engine=Engine(
        backend="jnp", activation="silu"), max_batch=8)
    with _profiled(tmp_path / "jnp"):
        jnp_plans(x)
    assert counter_totals() == {}             # no megakernel call


def test_no_session_leaves_spans_null_and_totals_still(make_stack):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert NULL_TRACER.span("plans.call") is _NULL_SPAN
    assert Tracer(enabled=False).span("batch.execute", bucket=1) \
        is _NULL_SPAN
    plans = BucketedPlanSet.compile(make_stack(),
                                    engine=Engine(backend="jnp"), max_batch=8)
    before = span_totals()
    _serve_and_chunk(plans, FakeClock(), batches=1)
    assert span_totals() == before
    assert NULL_TRACER.spans() == []


# --------------------------------------------------------------------------- #
# Prometheus exposition
# --------------------------------------------------------------------------- #

@pytest.fixture
def gated_server(make_stack):
    clock = FakeClock()
    engine = Engine(backend="jnp", gate=True, reorder_iters=20)
    plans = BucketedPlanSet.compile(make_stack(), engine=engine, max_batch=8)
    srv = SparseServer(plans, clock=clock, name="gated",
                       measure_dynamic_every=1)
    rng = np.random.default_rng(0)
    for _ in range(4):
        srv.submit(rng.standard_normal(plans.n_in).astype(np.float32))
    clock.advance(0.01)
    srv.drain()
    return srv


def test_prometheus_exposes_dynamic_vs_static_io(gated_server):
    snap = gated_server.snapshot()
    assert snap["model"] == "gated"
    io = snap["io"]
    assert io["batches_measured"] >= 1
    assert io["dynamic_blocks"] <= io["static_scheduled"]

    text = render_prometheus(snap)
    assert "# TYPE repro_served gauge" in text
    assert "repro_served 4" in text
    assert 'repro_latency_ms{quantile="0.5"}' in text
    assert "repro_latency_ms_count 4" in text
    # the acceptance gauge: per-bucket dynamic vs static block reads
    assert 'repro_io_dynamic_blocks{bucket="4"}' in text
    assert 'repro_io_static_scheduled{bucket="4"}' in text
    assert 'repro_io_read_fraction{bucket="4"}' in text
    assert 'repro_io_occupancy_hist{bin="dead",bucket="4"}' in text
    # weight-stream byte accounting, dtype-labelled (f32 plan → one entry)
    assert 'repro_io_weight_bytes{bucket="4",dtype="f32"}' in text
    # booleans flatten to 0/1, strings are skipped
    assert 'repro_io_within_bounds{bucket="4"} 1' in text
    assert "gated" not in text.replace('model="gated"', "")


def test_prometheus_router_snapshot_has_model_labels(make_stack):
    clock = FakeClock()
    router = ModelRouter.compile(
        {"a": make_stack(), "b": make_stack(seed=1)},
        engine=Engine(backend="jnp"), max_batch=8, clock=clock)
    router.submit("a", np.zeros(router.servers["a"].plans.n_in, np.float32))
    clock.advance(0.01)
    router.drain()
    snap = router.snapshot()
    assert set(snap["models"]) == {"a", "b"}
    assert snap["models"]["a"]["served"] == 1
    text = render_prometheus(snap)
    assert 'repro_served{model="a"} 1' in text
    assert 'repro_served{model="b"} 0' in text
    assert "repro_total_served 1" in text


def test_metrics_http_server(gated_server):
    with MetricsServer(gated_server.snapshot, port=0) as msrv:
        assert msrv.port != 0
        body = urllib.request.urlopen(msrv.url, timeout=5).read().decode()
        assert "repro_served 4" in body
        assert 'repro_io_dynamic_blocks{bucket="4"}' in body
        health = urllib.request.urlopen(
            f"http://{msrv.host}:{msrv.port}/healthz", timeout=5)
        assert health.read().decode().strip() == "ok"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://{msrv.host}:{msrv.port}/nope", timeout=5)
        assert ei.value.code == 404


def test_metrics_http_500_on_broken_snapshot():
    def boom():
        raise RuntimeError("snapshot broke")

    with MetricsServer(boom, port=0) as msrv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(msrv.url, timeout=5)
        assert ei.value.code == 500
