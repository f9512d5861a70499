"""Serving driver: batched prefill + decode with continuous batching slots.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --reduced \
        --batch 4 --prompt-len 32 --gen 16

A minimal production-shaped server loop: a request queue, fixed decode slots
(continuous batching: finished sequences are swapped for queued prompts), and
greedy decoding.  On CPU the reduced configs keep it interactive; the same
code path serves the full configs on a real mesh.

``--sparse-ffnn`` serves the paper's workload instead: feature vectors through
a magnitude-pruned block-sparse FFNN, routed through the ``repro.serving``
runtime — one engine compile (or a plan-store hit, which skips annealing
entirely via ``--plan-store DIR``) fanned out across power-of-two batch
buckets, with a deadline-aware wait-or-fire scheduler and SLO metrics:

    PYTHONPATH=src python -m repro.launch.serve --sparse-ffnn --requests 64

``--async`` serves through the background scheduler thread (real clock,
graceful SIGTERM drain); ``--models K`` serves K differently-pruned model
variants from one process via a shared-scheduler ``ModelRouter``:

    PYTHONPATH=src python -m repro.launch.serve --sparse-ffnn --async \
        --models 2 --requests 64

``--workers N`` runs the async scheduler as a staged pipeline (admission ->
batch formation -> per-bucket dispatch lanes -> an N-worker execution pool)
so different-bucket batches overlap; ``--http-port P`` (implies ``--async``)
opens the stdlib JSON front door (``POST /v1/infer``) and drives the request
loop through real HTTP clients, with queue-full admission surfacing as 429:

    PYTHONPATH=src python -m repro.launch.serve --sparse-ffnn \
        --http-port 0 --workers 2 --requests 64

Observability: ``--metrics-port P`` exposes a Prometheus text endpoint
(``/metrics``, port 0 = ephemeral) with the full serving snapshot — SLO
metrics, resilience state, and the per-bucket static-vs-dynamic I/O gauges
from the engine's block-read accounting; ``--trace-out PATH`` records the
request lifecycle (submit -> queue -> batch -> result, plus compile phases
and breaker transitions) and dumps a Chrome-trace JSON (or ``.jsonl``) on
exit, including graceful SIGTERM drain:

    PYTHONPATH=src python -m repro.launch.serve --sparse-ffnn --gate \
        --requests 64 --metrics-port 0 --trace-out /tmp/serve_trace.json
"""

from __future__ import annotations

import argparse
import sys
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.cachedir import enable_compile_cache
from repro.compat import set_mesh
from repro.configs import ARCH_IDS, get_config, reduced
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import make_serve_step
from repro.models import encdec, lm
from repro.models.sharding import axes_from_mesh


def _make_ffnn_layers(sizes, density, block, seed=0):
    from repro.sparse import prune_dense_stack

    rng = np.random.default_rng(seed)
    ws = [rng.standard_normal((sizes[i], sizes[i + 1])).astype(np.float32) * 0.03
          for i in range(len(sizes) - 1)]
    bs = [np.zeros(s, np.float32) for s in sizes[1:]]
    return prune_dense_stack(ws, bs, density=density,
                             block_m=block, block_n=block)


def _drive_http(front, args, sizes, names, rng, stop) -> dict:
    """Drive the request load through the HTTP front door with a small
    pool of real client connections (stdlib urllib).  Returns a status
    -> count map; a 429 (queue full) backs off per ``Retry-After`` and
    retries the same request, so admission control is load-shaping, not
    request loss."""
    import json
    import threading
    import urllib.error
    import urllib.request
    from collections import Counter

    work = deque((names[k % len(names)] if names else None,
                  rng.standard_normal(sizes[0]).astype(np.float32))
                 for k in range(args.requests))
    counts: Counter = Counter()
    lock = threading.Lock()

    def client() -> None:
        while not stop["flag"]:
            with lock:
                if not work:
                    return
                name, x = work.popleft()
            body = {"x": x.tolist()}
            if name is not None:
                body["model"] = name
            req = urllib.request.Request(
                front.url + "/v1/infer",
                data=json.dumps(body).encode("utf-8"),
                headers={"Content-Type": "application/json"},
                method="POST")
            retry_after = None
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    code = resp.status
                    resp.read()
            except urllib.error.HTTPError as e:
                code = e.code
                retry_after = e.headers.get("Retry-After")
                e.read()
            except OSError:
                code = -1
            with lock:
                counts[code] += 1
            if code == 429 and not stop["flag"]:
                time.sleep(float(retry_after or 0.05))
                with lock:
                    work.appendleft((name, x))

    threads = [threading.Thread(target=client, name=f"http-client-{i}")
               for i in range(args.http_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return dict(counts)


def serve_sparse_ffnn(args) -> int:
    """Serve the paper's sparse-FFNN workload through the serving runtime.

    The offline cost (block DAG, Theorem-1 order, CR, lowering) is paid once
    per model in ``Engine.compile`` — or not at all on a warm start from the
    plan store; the request loop only executes bucketed cached plans.

    ``--async`` runs the background scheduler thread against the real clock
    (the production mode); the default remains the deterministic step-driven
    loop.  ``--models K`` serves K differently-pruned variants through one
    ``ModelRouter``/scheduler.  SIGTERM (and SIGINT) trigger a graceful
    drain: queued requests are served, then the process exits.

    Returns the process exit code: 1 when any request came back without a
    result or any batch failed, else 0.
    """
    import signal

    from repro.engine import Engine, Mesh
    from repro.obs import MetricsServer, Tracer
    from repro.serving import (
        BucketedPlanSet,
        CircuitBreaker,
        HttpFrontDoor,
        ModelRouter,
        PlanStore,
        RetryPolicy,
        SparseServer,
    )

    if args.http_port is not None:
        # the front door needs a live scheduler behind it
        args.async_mode = True

    rng = np.random.default_rng(0)
    sizes = args.ffnn_sizes
    # one tracer for the whole process: engine compile phases, plan-store
    # hits/misses, and every request's lifecycle land in a single export
    tracer = Tracer() if args.trace_out else None
    engine = Engine(backend=args.backend, activation="gelu", reorder=True,
                    reorder_iters=args.reorder_iters,
                    fuse=not args.no_fuse, gate=args.gate,
                    weight_dtype=args.weight_dtype, tracer=tracer)
    mesh = Mesh.parse(args.mesh) if args.mesh else None
    store = (PlanStore(args.plan_store, tracer=tracer)
             if args.plan_store else None)
    # gating makes the measured dynamic-I/O path available: sample every
    # batch so the metrics endpoint carries live dynamic-vs-static gauges
    measure_every = 1 if args.gate else 0

    # resilience knobs: a breaker needs the safe twin to degrade to;
    # --safe-mode serves the twin directly (so a breaker is moot there)
    want_breaker = args.breaker > 0 and not args.safe_mode
    retry = None
    if args.retries > 0 or args.batch_timeout_ms is not None:
        retry = RetryPolicy(
            max_retries=args.retries,
            timeout_s=(args.batch_timeout_ms / 1e3
                       if args.batch_timeout_ms is not None else None))

    multi = args.models > 1
    t0 = time.time()
    if multi:
        if args.safe_mode:
            raise SystemExit("--safe-mode is single-model only; use "
                             "--breaker to degrade per model instead")
        # K differently-pruned variants of the same architecture, one
        # compile (or store hit) each, served through one shared scheduler
        nets = {f"m{k}": _make_ffnn_layers(sizes, args.density, args.block,
                                           seed=k)
                for k in range(args.models)}
        router = ModelRouter.compile(
            nets, engine=engine, max_batch=args.batch, plan_store=store,
            meshes={name: mesh for name in nets} if mesh else None,
            max_queue=args.max_queue, slo_ms=args.slo_ms, retry=retry,
            tracer=tracer, measure_dynamic_every=measure_every,
            breaker=(lambda: CircuitBreaker(
                threshold=args.breaker,
                cooldown_s=args.breaker_cooldown_ms / 1e3))
            if want_breaker else None,
            executor_workers=args.workers)
        names = list(router.servers)
        for name, srv in router.servers.items():
            print(f"[{name}] {srv.plans.describe()}")
    else:
        layers = _make_ffnn_layers(sizes, args.density, args.block)
        plans = BucketedPlanSet.compile(layers, engine=engine,
                                        max_batch=args.batch,
                                        plan_store=store, mesh=mesh,
                                        safe_twin=want_breaker)
        start = "warm (plan-store hit)" if plans.cache_hit else "cold"
        print(f"engine compile: {time.time() - t0:.1f}s [{start}] — "
              f"{plans.describe()}")
        if args.safe_mode:
            # the degraded path as the primary: jnp backend, gate off —
            # the same bit-exact forward the breaker would swap to
            plans = plans.build_safe_twin(jit=engine.jit)
            print(f"safe mode: {plans.describe()}")
        plans.warmup()
        server = SparseServer(
            plans, max_queue=args.max_queue, slo_ms=args.slo_ms,
            engine=engine, plan_store=store, mesh=mesh, retry=retry,
            tracer=tracer, measure_dynamic_every=measure_every,
            breaker=CircuitBreaker(threshold=args.breaker,
                                   cooldown_s=args.breaker_cooldown_ms / 1e3)
            if want_breaker else None,
            executor_workers=args.workers)

    # graceful drain on SIGTERM/SIGINT: stop submitting, serve everything
    # queued, report, exit — no request accepted before the signal is lost
    stop = {"flag": False}

    def _drain_handler(signum, frame):
        stop["flag"] = True

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _drain_handler)

    runtime = router if multi else server
    metrics_srv = None
    if args.metrics_port is not None:
        metrics_srv = MetricsServer(runtime.snapshot,
                                    port=args.metrics_port).start()
        print(f"metrics endpoint: {metrics_srv.url}")
    if args.async_mode:
        runtime.start()
        print("async scheduler thread started"
              + (f" (pipeline: {args.workers} executor workers)"
                 if args.workers else ""))
    front = None
    if args.http_port is not None:
        front = HttpFrontDoor(runtime, port=args.http_port).start()
        print(f"http front door: {front.url}  "
              f"(POST /v1/infer, GET /v1/result/<rid>)")

    rids = []   # (model or None, rid)
    http_codes = {}
    if front is not None:
        http_codes = _drive_http(front, args, sizes,
                                 names if multi else None, rng, stop)
        print(f"http clients done: {dict(sorted(http_codes.items()))} "
              f"over {args.http_clients} connections")
    else:
        pending = args.requests
        # bursty arrivals: submit a random clump, let the wait-or-fire
        # policy form batches, repeat — so the bucket router sees mixed
        # batch sizes
        while pending and not stop["flag"]:
            burst = int(rng.integers(1, args.batch + 1))
            for _ in range(min(burst, pending)):
                x = rng.standard_normal(sizes[0]).astype(np.float32)
                if multi:
                    name = names[len(rids) % len(names)]
                    rid = router.submit(name, x)
                else:
                    name, rid = None, server.submit(x)
                if rid is not None:
                    rids.append((name, rid))
                pending -= 1
                if not pending:
                    break
            if not args.async_mode:
                runtime.poll()
    if stop["flag"]:
        print("signal received: draining queued requests ...")
    # the pool snapshot lives until shutdown() releases the pipeline refs,
    # so sample it here — but only after the in-flight work finishes, or
    # the per-worker batch counts would reflect a near-empty pipeline
    if args.workers and args.async_mode and front is None:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and any(
                (router.servers[name] if multi else server).status(rid)
                == "pending" for name, rid in rids):
            time.sleep(0.005)
    pool_snap = (runtime.snapshot().get("pool")
                 if args.workers and args.async_mode else None)
    if front is not None:
        front.stop()
    if args.async_mode:
        runtime.shutdown(drain=True)
    else:
        runtime.drain()
    if pool_snap is not None:
        per = pool_snap.get("per_worker", {})
        util = {w: round(s.get("utilization", 0.0), 3)
                for w, s in sorted(per.items())}
        print(f"executor pool: {pool_snap.get('workers')} workers, "
              f"batches={ {w: s.get('batches') for w, s in sorted(per.items())} } "
              f"utilization={util}")

    # "served" comes from the metrics: collecting at the very end can see
    # fewer results than were served once capacity eviction kicks in (the
    # oldest uncollected results are dropped by design under heavy traffic)
    if multi:
        collected = (http_codes.get(200, 0) if front is not None else
                     sum(router.result(name, rid) is not None
                         for name, rid in rids))
        totals = router.metrics_snapshot()["total"]
        print(f"served {totals['served']} requests across {args.models} "
              f"models ({collected} collected)")
        print(router.summary())
    else:
        collected = (http_codes.get(200, 0) if front is not None else
                     sum(server.result(rid) is not None for _, rid in rids))
        totals = server.metrics.snapshot()
        print(f"served {server.metrics.served} sparse-FFNN requests "
              f"({collected} collected) — {server.metrics.summary()}")
        if want_breaker or retry is not None:
            m = server.metrics.snapshot()
            print(f"resilience: retries={m['retries']} "
                  f"timeouts={m['batch_timeouts']} "
                  f"breaker trips={m['breaker_trips']} "
                  f"resets={m['breaker_resets']} "
                  f"degraded batches={m['degraded_batches']}")
        print(f"bucket calls: "
              f"{ {b: n for b, n in plans.bucket_calls.items() if n} }")
        base = getattr(plans, "base", None)
        if args.gate and base is not None and \
                getattr(base, "_measure", None) is not None:
            # measured dynamic I/O of one representative batch: how many
            # scheduled weight blocks a demand-driven stream actually read
            xs = np.stack([rng.standard_normal(sizes[0]).astype(np.float32)
                           for _ in range(min(args.batch, 8))])
            print(base.measure_dynamic(xs).summary())

    if metrics_srv is not None:
        # scrape our own endpoint once so the run exercises the full HTTP
        # exposition path (the CI smoke greps these lines)
        import urllib.request
        with urllib.request.urlopen(metrics_srv.url, timeout=5) as resp:
            body = resp.read().decode("utf-8")
        lines = body.splitlines()
        print(f"metrics scrape: {len(lines)} lines from {metrics_srv.url}")
        for ln in lines[:8]:
            print(f"  {ln}")
        for ln in lines:
            if "_io_" in ln and not ln.startswith("#"):
                print(f"  {ln}")
        metrics_srv.stop()
    if args.trace_out and tracer is not None:
        path = tracer.export(args.trace_out)
        print(f"trace: {tracer.recorded} spans recorded "
              f"({tracer.dropped} dropped) -> {path}")

    # a request that came back None failed (evicted results were served);
    # over HTTP, any final status but 200 (429 is retried) is a failure
    if front is not None:
        lost = sum(n for code, n in http_codes.items() if code != 200)
    else:
        lost = len(rids) - collected - totals["results_evicted"]
    if lost or totals["batch_failures"]:
        print(f"FAILED: {lost} request(s) came back without a result, "
              f"{totals['batch_failures']} batch failure(s)",
              file=sys.stderr)
        return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="mamba2-1.3b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--sparse-ffnn", action="store_true",
                    help="serve the paper's sparse-FFNN workload via the "
                         "fused inference engine instead of an LM")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="drive the sparse serving loop from a background "
                         "scheduler thread (real clock) instead of the "
                         "step-driven caller loop; SIGTERM drains gracefully")
    ap.add_argument("--models", type=int, default=1,
                    help="serve N differently-pruned model variants from "
                         "one process through a shared-scheduler ModelRouter "
                         "(sparse-ffnn only)")
    ap.add_argument("--ffnn-sizes", type=int, nargs="+",
                    default=[1024, 4096, 1024])
    ap.add_argument("--density", type=float, default=0.1)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--reorder-iters", type=int, default=300)
    ap.add_argument("--no-fuse", action="store_true",
                    help="serve with per-layer dispatch instead of the fused "
                         "whole-network megakernel plan")
    ap.add_argument("--gate", action="store_true",
                    help="runtime tile-occupancy gating: skip weight blocks "
                         "whose input tile is all-zero for the batch "
                         "(bit-exact; prints the measured dynamic I/O report "
                         "after serving)")
    ap.add_argument("--mesh", default=None, metavar="MODELxDATA",
                    help="serve through a sharded execution plan, e.g. 4x2 "
                         "= 4 model shards x 2 data replicas (sparse-ffnn "
                         "only; falls back to a host loop when the machine "
                         "has fewer devices than mesh slots)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "interpret", "jnp"))
    ap.add_argument("--weight-dtype", default="f32",
                    choices=("f32", "bf16", "fp8"),
                    help="storage dtype of the streamed weight blocks: "
                         "bf16/fp8 quantize each block with one f32 scale "
                         "at compile time and fuse the dequant into the "
                         "kernel, halving/quartering weight-stream bytes "
                         "(outputs approximate within the documented "
                         "tolerance; f32 stays bit-exact)")
    ap.add_argument("--plan-store", default=None,
                    help="directory of the persistent plan cache; a warm "
                         "start skips the annealing cost entirely")
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="target end-to-end latency SLO for the sparse "
                         "serving scheduler")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="admission bound of the sparse serving queue")
    ap.add_argument("--safe-mode", action="store_true",
                    help="serve the plan's safe-mode twin directly (jnp "
                         "backend, gating off — the same bit-exact forward "
                         "the circuit breaker degrades to, as the primary)")
    ap.add_argument("--breaker", type=int, default=0, metavar="K",
                    help="arm a circuit breaker: K consecutive batch "
                         "failures/timeouts degrade to the precompiled "
                         "safe-mode twin, half-opening back after the "
                         "cool-down (0 = off)")
    ap.add_argument("--breaker-cooldown-ms", type=float, default=1000.0,
                    help="circuit-breaker cool-down before probing the "
                         "fast plan again")
    ap.add_argument("--retries", type=int, default=0,
                    help="bounded per-batch retry attempts (with "
                         "exponential backoff) before a batch fails")
    ap.add_argument("--workers", type=int, default=0, metavar="N",
                    help="execution-stage worker pool size: the async "
                         "scheduler becomes a staged pipeline (formation "
                         "-> per-bucket dispatch lanes -> N workers) so "
                         "different-bucket batches overlap; 0 keeps the "
                         "single-threaded scheduler (sparse-ffnn only)")
    ap.add_argument("--http-port", type=int, default=None, metavar="P",
                    help="open the JSON front door on this port (0 = "
                         "ephemeral) and drive the request load through "
                         "real HTTP clients; queue-full admission becomes "
                         "429 + Retry-After (implies --async; sparse-ffnn "
                         "only)")
    ap.add_argument("--http-clients", type=int, default=4,
                    help="concurrent HTTP client connections used by "
                         "--http-port to drive the load")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="P",
                    help="expose a Prometheus text endpoint (/metrics) on "
                         "this port with the live serving snapshot: SLO "
                         "quantiles, resilience state, per-bucket static/"
                         "dynamic block-read gauges (0 = ephemeral port; "
                         "sparse-ffnn only)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request/compile/breaker spans and write a "
                         "Chrome-trace JSON (.jsonl for line-delimited "
                         "spans) on exit — open in chrome://tracing or "
                         "Perfetto (sparse-ffnn only)")
    ap.add_argument("--batch-timeout-ms", type=float, default=None,
                    help="wall-clock bound on one batch execution attempt; "
                         "a hung attempt is abandoned and counted (and "
                         "retried under --retries)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.sparse_ffnn:
        return serve_sparse_ffnn(args)

    cfg = reduced(get_config(args.arch)) if args.reduced else get_config(args.arch)
    mesh = make_test_mesh(1, 1)
    axes_from_mesh(mesh)
    set_mesh(mesh)
    mod = encdec if cfg.family == "encdec" else lm
    params = mod.init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    serve_step = jax.jit(make_serve_step(cfg, mesh))

    rng = np.random.default_rng(0)
    window = args.prompt_len + args.gen
    queue = deque(
        rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32)
        for _ in range(args.requests))
    done = []
    t0 = time.time()
    tokens_out = 0
    while queue:
        # fill a batch of slots from the queue (continuous batching)
        slot_prompts = [queue.popleft()
                        for _ in range(min(args.batch, len(queue)))]
        if not slot_prompts:
            break
        B = len(slot_prompts)
        prompts = jnp.asarray(np.stack(slot_prompts))
        if cfg.family == "encdec":
            enc_in = jnp.asarray(
                rng.standard_normal((B, args.prompt_len, cfg.d_model)) * 0.05,
                jnp.float32)
            enc_out = encdec.encode(params, cfg, enc_in)
            caches = encdec.make_dec_caches(params, cfg, enc_out,
                                            window=window, dtype=jnp.float32)
            cur = jnp.zeros((B, 1), jnp.int32)
        else:
            logits, caches = lm.prefill(params, cfg, tokens=prompts)
            caches = lm.grow_caches(cfg, caches, window)
            cur = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        outs = [cur]
        for _ in range(args.gen - 1):
            cur, caches = serve_step(params, caches, cur)
            outs.append(cur)
        gen = np.concatenate([np.asarray(o) for o in outs], axis=1)
        tokens_out += gen.size
        done.extend(list(gen))
    dt = time.time() - t0
    print(f"arch={cfg.name} served {len(done)} sequences, "
          f"{tokens_out} tokens in {dt:.2f}s "
          f"({tokens_out/max(dt,1e-9):.1f} tok/s greedy)")
    print("sample:", done[0][:16].tolist() if done else "none")
    return 0


if __name__ == "__main__":
    sys.exit(main())
