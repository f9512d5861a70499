"""Serving-runtime benchmark: cold vs warm compile + bucketed vs fixed batching.

    PYTHONPATH=src python benchmarks/bench_serving.py [--max-batch 32]

Measures the two amortizations the serving subsystem adds on top of the
engine:

  * **plan persistence** — the same network compiled cold (Theorem-1
    schedule + Connection Reordering + lowering, then persisted) and warm
    (content-addressed plan-store hit: rebuilt from the stored connection
    order with ZERO annealer iterations).  Outputs are checked bit-identical
    across the two plans;
  * **bucketed plans** — a mixed-batch-size request trace served through
    power-of-two buckets (pad only up to the smallest bucket that fits)
    vs the old fixed-batch policy (every batch padded to ``max_batch``).
    Per-batch latency p50/p99 for both; small batches dominate real traces,
    so bucketed p50 must beat fixed p50;
  * **async vs step-driven serving** — the same request stream through the
    step-driven caller loop (submission and execution interleaved in one
    thread) and through the background scheduler thread with 4 concurrent
    submitters.  Async must not lose throughput, and typically wins by
    overlapping submission with batch execution;
  * **pipelined execution** — an open-loop (fixed-RPS) request sweep
    through the staged pipeline (formation -> per-bucket dispatch lanes ->
    executor pool) with 1 vs N workers.  Device time is SIMULATED: every
    batch call runs the real plan (outputs stay bit-identical and are
    checked against single-row references) and then sleeps out a fixed
    ``--sim-device-ms`` budget — modelling the paper's regime, where batch
    latency is dominated by I/O-bound accelerator streaming while the host
    sits idle.  The sleep releases the GIL, so worker overlap is real even
    on a single-core CI host; with N workers, different-bucket batches
    overlap and the saturated throughput must reach >= 1.3x the 1-worker
    pipeline (p99 latency recorded for both);
  * **tracer overhead** — the same step-driven stream with request tracing
    disabled and enabled.  A disabled tracer is asserted within noise of
    serving with no tracer at all (the hot path pays one attribute read per
    instrumentation site); the enabled-tracer throughput is recorded so the
    observability tax stays visible across PRs.

Results are printed AND written to machine-readable ``BENCH_serving.json``
(committed + uploaded as a CI artifact) so the serving perf trajectory is
tracked across PRs.  On CPU hosts the latency comparison runs on the ``jnp``
backend; on TPU pass ``--backend pallas``.
"""

from __future__ import annotations

import argparse
import json
import platform
import shutil
import tempfile
import threading
import time

import jax
import numpy as np

from repro.cachedir import enable_compile_cache
from repro.engine import Engine, Mesh
from repro.serving import BucketedPlanSet, PlanStore, SparseServer
from repro.serving.metrics import percentile
from repro.sparse import prune_dense_stack


def make_layers(sizes, density, block, seed=0):
    rng = np.random.default_rng(seed)
    ws = [rng.standard_normal((sizes[i], sizes[i + 1])).astype(np.float32) * 0.03
          for i in range(len(sizes) - 1)]
    bs = [np.zeros(s, np.float32) for s in sizes[1:]]
    return prune_dense_stack(ws, bs, density=density,
                             block_m=block, block_n=block)


def make_engine(args):
    return Engine(backend=args.backend, activation="gelu", reorder=True,
                  reorder_iters=args.reorder_iters)


def mixed_trace(rng, n_batches, max_batch):
    """Batch sizes of a bursty request trace: mostly small, some full."""
    sizes = [s for s in (1, 2, 3, 4, 6, 8, 12, 16, 24, 32) if s <= max_batch]
    probs = np.array([0.22, 0.18, 0.12, 0.12, 0.08, 0.08, 0.06, 0.06,
                      0.04, 0.04][:len(sizes)])
    probs = probs / probs.sum()
    return [int(rng.choice(sizes, p=probs)) for _ in range(n_batches)]


class SimDevicePlans:
    """A ``BucketedPlanSet`` whose batch calls take a fixed simulated
    device time.

    Every call runs the REAL underlying plan first (outputs stay
    bit-identical to the unwrapped plan set), then sleeps out the
    remainder of ``sim_s``.  ``time.sleep`` releases the GIL, so this
    models the paper's target regime — batch latency dominated by
    I/O-bound accelerator streaming while the host is idle — and lets
    executor-pool overlap show up even on a single-core host, where real
    host-side compute could never overlap with itself.  Everything else
    (bucket routing, dtype, warmup, ...) delegates to the wrapped set.
    """

    def __init__(self, base, sim_s: float):
        self._base = base
        self._sim_s = sim_s

    def __call__(self, x):
        t0 = time.perf_counter()
        y = self._base(x)
        pad = self._sim_s - (time.perf_counter() - t0)
        if pad > 0:
            time.sleep(pad)
        return y

    def __getattr__(self, name):
        return getattr(self._base, name)


def time_trace(run, trace, xs, iters_warm=2):
    """Per-batch wall latencies of ``run(x_n)`` over the trace sizes."""
    for n in sorted(set(trace)):
        for _ in range(iters_warm):
            run(xs[n])  # trace/warm every shape outside the timed loop
    lats = []
    for n in trace:
        t0 = time.perf_counter()
        run(xs[n])
        lats.append(time.perf_counter() - t0)
    return lats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[768, 1536, 1536, 768])
    ap.add_argument("--density", type=float, default=0.2)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--batches", type=int, default=60,
                    help="mixed-size trace length (in batches)")
    ap.add_argument("--reorder-iters", type=int, default=200)
    ap.add_argument("--slo-ms", type=float, default=50.0)
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "interpret", "jnp"))
    ap.add_argument("--plan-dir", default=None,
                    help="plan-store dir (default: fresh temp dir, so the "
                         "cold/warm comparison is reproducible)")
    ap.add_argument("--mesh", default=None, metavar="MODELxDATA",
                    help="benchmark through a sharded execution plan "
                         "(e.g. 4x2); default unsharded")
    ap.add_argument("--sim-device-ms", type=float, default=25.0,
                    help="simulated per-batch device time for the pipeline "
                         "sweep (the real plan still runs; the call sleeps "
                         "out the remainder)")
    ap.add_argument("--pipeline-requests", type=int, default=240,
                    help="requests per pipeline sweep point")
    ap.add_argument("--pipeline-rates", type=float, nargs="+",
                    default=[150.0, 300.0, 600.0],
                    help="open-loop offered rates (req/s) for the pipeline "
                         "sweep; the >=1.3x assertion applies at the "
                         "highest (saturating) rate")
    ap.add_argument("--pipeline-workers", type=int, default=4,
                    help="executor-pool size compared against 1 worker in "
                         "the pipeline sweep")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = Mesh.parse(args.mesh) if args.mesh else None

    rng = np.random.default_rng(0)
    layers = make_layers(args.sizes, args.density, args.block)

    plan_dir = args.plan_dir or tempfile.mkdtemp(prefix="plan_store_")
    store = PlanStore(plan_dir)
    # a reused --plan-dir may already hold this entry; evict it so the cold
    # measurement is genuinely cold on every run
    store.evict(make_engine(args), layers, mesh=mesh)

    # ---- cold start: schedule + CR + lowering, then persisted ---------- #
    t0 = time.perf_counter()
    plan_cold, hit = store.get_or_compile(make_engine(args), layers,
                                          mesh=mesh)
    cold_s = time.perf_counter() - t0
    assert not hit, "expected a cold start against a fresh plan store"
    print(f"cold compile:  {cold_s:6.2f}s "
          f"({plan_cold.annealer_iters} annealer iters)")

    # ---- warm start: content-addressed hit, zero annealing ------------- #
    t0 = time.perf_counter()
    plan_warm, hit = store.get_or_compile(make_engine(args), layers,
                                          mesh=mesh)
    warm_s = time.perf_counter() - t0
    assert hit, "expected a plan-store hit on the second compile"
    assert plan_warm.annealer_iters == 0, "warm start must skip annealing"
    print(f"warm compile:  {warm_s:6.2f}s (plan-store hit, "
          f"{plan_warm.annealer_iters} annealer iters, "
          f"{cold_s / max(warm_s, 1e-9):.0f}x faster)")

    x_full = rng.standard_normal(
        (args.max_batch, args.sizes[0])).astype(np.float32)
    y_cold = np.asarray(plan_cold(x_full))
    y_warm = np.asarray(plan_warm(x_full))
    assert np.array_equal(y_cold, y_warm), \
        "warm-start outputs must be bit-identical to the cold compile"
    print("warm outputs bit-identical to cold: OK")

    # ---- bucketed vs fixed-batch latency on a mixed-size trace --------- #
    plans = BucketedPlanSet.compile(layers, engine=make_engine(args),
                                    max_batch=args.max_batch,
                                    plan_store=store, mesh=mesh)
    plans.warmup()
    trace = mixed_trace(rng, args.batches, args.max_batch)
    xs = {n: rng.standard_normal((n, args.sizes[0])).astype(np.float32)
          for n in sorted(set(trace))}

    lat_bucketed = time_trace(plans, trace, xs)

    # the old fixed-batch policy: every batch padded up to max_batch
    def fixed(x):
        n = x.shape[0]
        if n < args.max_batch:
            x = np.concatenate(
                [x, np.zeros((args.max_batch - n, x.shape[1]), x.dtype)])
        return np.asarray(plans.plans[args.max_batch](x))[:n]

    lat_fixed = time_trace(fixed, trace, xs)

    b50, b99 = percentile(lat_bucketed, 50), percentile(lat_bucketed, 99)
    f50, f99 = percentile(lat_fixed, 50), percentile(lat_fixed, 99)
    print(f"trace: {len(trace)} batches, sizes p50={percentile([float(t) for t in trace], 50):.0f}, "
          f"mean={np.mean(trace):.1f}, max={max(trace)}")
    print(f"  bucketed: p50 {1e3*b50:7.2f} ms  p99 {1e3*b99:7.2f} ms")
    print(f"  fixed:    p50 {1e3*f50:7.2f} ms  p99 {1e3*f99:7.2f} ms "
          f"(pad to {args.max_batch})")
    assert b50 < f50, "bucketed p50 must beat fixed-batch p50 on a mixed trace"

    # ---- end-to-end serve loop through the scheduler ------------------- #
    server = SparseServer(plans, slo_ms=args.slo_ms)
    for n in trace:
        for _ in range(n):
            server.submit(rng.standard_normal(
                args.sizes[0]).astype(np.float32))
        server.poll()
    server.drain()
    print("serve loop:", server.metrics.summary())

    # ---- async vs step-driven serve-loop throughput -------------------- #
    # same request stream both ways: the step-driven loop interleaves
    # submission and execution in one thread; async mode overlaps them —
    # submitter threads keep the queue fed while the scheduler thread
    # executes, so batches stay full and wall time drops.  The stream is
    # long enough that per-run constants (thread spawn, jit-cache touch)
    # amortize away and steady-state throughput is what's measured.
    n_req = max(2048, int(sum(trace)))
    req_rows = [rng.standard_normal(args.sizes[0]).astype(np.float32)
                for _ in range(n_req)]

    def run_step(tracer=None) -> float:
        server = SparseServer(plans, slo_ms=args.slo_ms, max_queue=n_req,
                              tracer=tracer)
        t0 = time.perf_counter()
        for x in req_rows:
            server.submit(x)
            server.poll()
        server.drain()
        dt = time.perf_counter() - t0
        assert server.metrics.served == n_req
        return n_req / dt

    def run_async(n_threads: int = 4) -> float:
        server = SparseServer(plans, slo_ms=args.slo_ms,
                              max_queue=n_req).start()
        shards = [req_rows[i::n_threads] for i in range(n_threads)]
        gate = threading.Barrier(n_threads + 1)

        def client(shard):
            gate.wait()
            for x in shard:
                server.submit(x)

        ts = [threading.Thread(target=client, args=(s,)) for s in shards]
        for t in ts:
            t.start()
        gate.wait()                      # all submitters ready: go
        t0 = time.perf_counter()
        for t in ts:
            t.join()
        server.shutdown(drain=True)
        dt = time.perf_counter() - t0
        assert server.metrics.served == n_req
        return n_req / dt

    # best-of-3: the first run of either mode pays one-off warm-in costs
    # (thread pools, page cache); steady-state throughput is the comparison
    step_rps = max(run_step() for _ in range(3))
    async_rps = max(run_async() for _ in range(3))
    print(f"  step-driven: {step_rps:8.0f} req/s")
    print(f"  async:       {async_rps:8.0f} req/s "
          f"({async_rps / step_rps:.2f}x, 4 submit threads)")
    assert async_rps >= 0.9 * step_rps, \
        "async serving should not lose throughput to the step-driven loop"

    # ---- pipelined execution: open-loop RPS sweep, 1 vs N workers ------ #
    # device time is simulated (see SimDevicePlans): the real plan runs,
    # the call then sleeps out --sim-device-ms.  That is the paper's
    # regime — batch latency dominated by I/O-bound weight streaming on
    # the accelerator while the host idles — and it makes the sweep
    # deterministic and host-independent.  1-worker capacity is one
    # max-bucket batch per sim tick; N workers overlap different-bucket
    # batches (the spill policy forms smaller-bucket batches while the
    # preferred lane is busy), so saturated throughput must scale.
    sim_s = args.sim_device_ms / 1e3
    n_pipe = args.pipeline_requests
    # a dedicated small-max-batch plan set: worker overlap comes from the
    # SPILL lanes (buckets below the preferred one), whose combined rows
    # are 1+2+4 = 7/8 of the max bucket at max_batch=8 — so N workers can
    # approach ~1.9x one worker.  At max_batch=32 the smaller buckets sum
    # to less than one full lane (31/32) and the ceiling collapses to
    # ~1.25x: the sweep would measure lane arithmetic, not the pipeline
    pipe_max = min(8, args.max_batch)
    pipe_plans = BucketedPlanSet.compile(layers, engine=make_engine(args),
                                         max_batch=pipe_max,
                                         plan_store=store, mesh=mesh)
    pipe_plans.warmup()
    pool_x = [rng.standard_normal(args.sizes[0]).astype(np.float32)
              for _ in range(16)]
    # single-row references through the UNwrapped plans: the pipeline's
    # outputs must match bit-for-bit regardless of worker count, bucket
    # routing, or batch composition
    expected = [np.asarray(pipe_plans(x[None, :]))[0] for x in pool_x]

    def run_pipeline(workers: int, rate) -> dict:
        """One sweep point: open-loop arrivals at ``rate`` req/s, or a
        single up-front burst (``rate=None``) that keeps the queue
        saturated — the capacity-bound regime the scaling assertion
        uses, free of arrival-pacing jitter."""
        server = SparseServer(SimDevicePlans(pipe_plans, sim_s),
                              slo_ms=args.slo_ms, max_queue=n_pipe,
                              executor_workers=workers)
        server.start()
        rids = []
        t0 = time.perf_counter()
        for i in range(n_pipe):
            if rate is not None:                # open-loop arrivals
                target = t0 + i / rate
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
            rid = server.submit(pool_x[i % len(pool_x)])
            assert rid is not None, "pipeline sweep must not reject"
            rids.append(rid)
        outs = [server.wait(rid, timeout=120.0) for rid in rids]
        dt = time.perf_counter() - t0
        snap = server.snapshot()                # pool stats live until
        server.shutdown(drain=True)             # shutdown releases them
        assert server.metrics.served == n_pipe, "zero lost requests"
        for i, o in enumerate(outs):
            assert o is not None and np.array_equal(
                np.asarray(o), expected[i % len(pool_x)]), \
                f"request {i}: pipeline output != single-row reference"
        per_worker = {w: s["batches"] for w, s in
                      snap.get("pool", {}).get("per_worker", {}).items()}
        return {
            "workers": workers,
            "offered_rps": rate,
            "effective_rps": n_pipe / dt,
            "latency_p99_ms": snap["latency_ms"]["p99"],
            "dispatch_wait_p99_ms": snap["dispatch_wait_ms"]["p99"],
            "batches": snap["batches"],
            "per_worker_batches": per_worker,
            "bit_identical": True,
        }

    sweep = []
    for rate in sorted(args.pipeline_rates) + [None]:
        for workers in (1, args.pipeline_workers):
            r = run_pipeline(workers, rate)
            sweep.append(r)
            offered = (f"{rate:5.0f} req/s" if rate is not None
                       else "saturated")
            print(f"  pipeline offered={offered} workers={workers}: "
                  f"{r['effective_rps']:6.0f} req/s effective, "
                  f"p99 {r['latency_p99_ms']:8.1f} ms, "
                  f"batches={r['per_worker_batches']}")
    # the scaling assertion runs on the SATURATED (burst) points: both
    # configs are capacity-bound there, so the ratio measures lane
    # overlap, not arrival-pacing jitter
    pipe1 = next(r for r in sweep
                 if r["offered_rps"] is None and r["workers"] == 1)
    pipeN = next(r for r in sweep
                 if r["offered_rps"] is None
                 and r["workers"] == args.pipeline_workers)
    pipe_speedup = pipeN["effective_rps"] / pipe1["effective_rps"]
    print(f"  pipeline speedup at saturation: "
          f"{pipe_speedup:.2f}x ({args.pipeline_workers} vs 1 workers, "
          f"sim device {args.sim_device_ms:.0f} ms/batch, "
          f"outputs bit-identical)")
    assert pipe_speedup >= 1.3, \
        (f"{args.pipeline_workers} executor workers must reach >= 1.3x the "
         f"1-worker pipeline at saturation (got {pipe_speedup:.2f}x)")

    # ---- tracer overhead: disabled vs enabled on the hot path ---------- #
    # a DISABLED tracer must cost one attribute read per instrumentation
    # site — indistinguishable from no tracer at all (within measurement
    # noise); an ENABLED tracer pays span/event recording per request and
    # is reported so the observability tax stays visible across PRs
    from repro.obs import Tracer

    tracer_off_rps = max(run_step(Tracer(enabled=False)) for _ in range(3))
    tracer_on_rps = max(run_step(Tracer(capacity=4096)) for _ in range(3))
    print(f"  tracer off:  {tracer_off_rps:8.0f} req/s "
          f"({tracer_off_rps / step_rps:.2f}x of no-tracer baseline)")
    print(f"  tracer on:   {tracer_on_rps:8.0f} req/s "
          f"({tracer_on_rps / tracer_off_rps:.2f}x of disabled)")
    assert tracer_off_rps >= 0.8 * step_rps, \
        "a disabled tracer must be within noise of serving with no tracer"

    result = {
        "net": {
            "sizes": args.sizes,
            "density": args.density,
            "block": args.block,
            "nnz_blocks": int(sum(l.nnz_blocks for l in layers)),
        },
        "backend": plan_cold.backend,
        "reorder_iters": args.reorder_iters,
        "compile_s": {
            "cold": cold_s,
            "warm": warm_s,
            "warm_speedup": cold_s / max(warm_s, 1e-9),
            "warm_annealer_iters": plan_warm.annealer_iters,
            "bit_identical_outputs": True,
        },
        "trace": {
            "batches": len(trace),
            "max_batch": args.max_batch,
            "mean_batch": float(np.mean(trace)),
            "buckets": list(plans.buckets),
        },
        "latency_ms": {
            "bucketed_p50": 1e3 * b50,
            "bucketed_p99": 1e3 * b99,
            "fixed_p50": 1e3 * f50,
            "fixed_p99": 1e3 * f99,
            "bucketed_vs_fixed_p50_speedup": f50 / max(b50, 1e-12),
        },
        "serve_loop": server.metrics.snapshot(),
        "serve_modes": {
            "step_rps": step_rps,
            "async_rps": async_rps,
            "async_vs_step": async_rps / step_rps,
            "submit_threads": 4,
        },
        "serve_pipeline": {
            "sim_device_ms": args.sim_device_ms,
            "max_batch": pipe_max,
            "requests_per_point": n_pipe,
            "workers_compared": [1, args.pipeline_workers],
            "sweep": sweep,
            "saturated_speedup": pipe_speedup,
            "bit_identical_outputs": True,
        },
        "tracer": {
            "off_rps": tracer_off_rps,
            "on_rps": tracer_on_rps,
            "disabled_vs_baseline": tracer_off_rps / step_rps,
            "enabled_vs_disabled": tracer_on_rps / tracer_off_rps,
        },
        "env": {
            "jax": jax.__version__,
            "jax_backend": jax.default_backend(),
            "python": platform.python_version(),
            # device count + mesh shape make the perf trajectory comparable
            # across environments (single vs forced-multi-device hosts)
            "devices": jax.device_count(),
            "mesh": {"model": mesh.model if mesh else 1,
                     "data": mesh.data if mesh else 1},
        },
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")
    if args.plan_dir is None:
        shutil.rmtree(plan_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
