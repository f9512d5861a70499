#!/usr/bin/env python3
"""Serve the BERT-large encoder FFN on a TPU through the Pallas megakernel.

    python chip_smoke.py              # one chip: f32, gated, bf16, fp8 phases
    python chip_smoke.py --chips 4    # only Mesh(4, 1) against the unsharded plan

The network is the BERT-large encoder FFN at its published widths
(1024 -> 4096 -> 1024, block 128, density 0.1, gelu hidden epilogue),
block-magnitude-pruned with ``prune_dense_stack`` from random weights made
from ``--seed``.  The biases are nonzero so that the bias tiles are checked
too.

Every phase serves that network through the normal path:
``Engine(backend="pallas")`` -> ``BucketedPlanSet.compile(max_batch=32)`` ->
``warmup()`` -> ``SparseServer`` with its async scheduler thread.  It submits
bursts of single-row requests that land in several buckets, waits for each
one, shuts down with a drain, and checks:

  * the plan ran the fused Pallas megakernel with no fallback;
  * no batch failed and none was served by a degraded twin;
  * every request came back, within a tolerance of a float32 reference of
    the same layers (``kernels/ref.py``, full-precision dot): max-abs error
    over the absmax of the reference.

A quarter of every request's input tiles is zero, so the gated phase has
dead tiles to skip.

The script refuses to run without a TPU: it never falls back to the CPU.
Any failed check exits non-zero.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.cachedir import enable_compile_cache  # noqa: E402
from repro.core import _iosim_c  # noqa: E402
from repro.engine import Engine, Mesh, ShardedExecutionPlan  # noqa: E402
from repro.kernels.ops import bsr_layer_ref  # noqa: E402
from repro.serving import BucketedPlanSet, SparseServer  # noqa: E402
from repro.sparse import prune_dense_stack  # noqa: E402

SIZES = (1024, 4096, 1024)
DENSITY = 0.1
BLOCK = 128
MAX_BATCH = 32
# requests per burst: each burst is submitted at once and waited for, so
# batch sizes (and buckets) vary from burst to burst
BURSTS = (1, 2, 3, 5, 8, 13, 20)
REORDER_ITERS = 300
WAIT_S = 300.0
# max-abs error over the reference's absmax.  bf16 and fp8 are the weight-
# stream contract of docs/engine.md.  f32 is held to the bf16 one: on the
# v5e an f32 dot runs at the MXU's default precision, one pass with the
# operands rounded to bf16 (f32 accumulation), so an f32 plan's error is the
# bf16 plan's (3.4e-3 each on the seed-0 network, TPU v5 lite).
TOL = {"f32": 1e-2, "bf16": 1e-2, "fp8": 1e-1}
# (name, weight dtype, gate)
PHASES = (("f32", "f32", False), ("gated", "f32", True),
          ("bf16", "bf16", False), ("fp8", "fp8", False))


def make_layers(seed, sizes=SIZES, density=DENSITY, block=BLOCK):
    """The pruned network: weights N(0, 0.03^2), biases N(0, 0.1^2)."""
    rng = np.random.default_rng(seed)
    ws = [rng.standard_normal((a, b)).astype(np.float32) * 0.03
          for a, b in zip(sizes[:-1], sizes[1:])]
    bs = [rng.standard_normal(b).astype(np.float32) * 0.1 for b in sizes[1:]]
    return prune_dense_stack(ws, bs, density=density,
                             block_m=block, block_n=block)


def make_requests(seed, n, n_in, block=BLOCK):
    """``n`` request rows; the first quarter of the input tiles is zero."""
    xs = np.random.default_rng(seed + 1).standard_normal((n, n_in))
    xs[:, :(n_in // block // 4) * block] = 0.0
    return xs.astype(np.float32)


def reference(layers, xs):
    """float32 reference of the same layers: gelu hidden, linear output."""
    h = xs
    for k, lay in enumerate(layers):
        act = jax.nn.gelu if k < len(layers) - 1 else None
        h = bsr_layer_ref(h, lay, act)
    return np.asarray(h)


def rel_err(y, ref) -> float:
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def serve(plans, xs, bursts=BURSTS):
    """Serve ``xs`` row by row through an async ``SparseServer``; returns
    the rows (None where a request was refused or failed) and the server."""
    server = SparseServer(plans).start()
    rows, i = [], 0
    try:
        for n in bursts:
            rids = [server.submit(x) for x in xs[i:i + n]]
            i += n
            rows += [None if rid is None else server.wait(rid, WAIT_S)
                     for rid in rids]
    finally:
        server.shutdown(drain=True)
    return rows, server


def check_served(name, rows, server, ref, tol, problems) -> str:
    """Server-side checks shared by every phase; returns the report line."""
    m = server.metrics.snapshot()
    if server.breaker is not None:
        problems.append(f"{name}: server has a breaker")
    for key in ("batch_failures", "degraded_batches"):
        if m[key]:
            problems.append(f"{name}: {key} = {m[key]}")
    missing = sum(r is None for r in rows)
    err = float("nan")
    if missing:
        problems.append(f"{name}: {missing}/{len(rows)} requests came back "
                        "None")
    else:
        err = rel_err(np.stack(rows), ref)
        if not err <= tol:
            problems.append(f"{name}: max error {err!r} above {tol!r}")
    buckets = {int(b): n for b, n in m["bucket_hist"].items()}
    return (f"max_rel_err={err!r} tol={tol!r} served={m['served']} "
            f"requests={len(rows)} buckets={buckets} "
            f"batch_failures={m['batch_failures']} "
            f"degraded_batches={m['degraded_batches']}")


def check_plan(name, plan, backend, problems) -> None:
    if plan.backend != backend:
        problems.append(f"{name}: plan backend {plan.backend!r}, "
                        f"want {backend!r}")
    if not plan.fused:
        problems.append(f"{name}: plan is not fused")
    if plan.fallback_reason is not None:
        problems.append(f"{name}: fallback: {plan.fallback_reason}")


def run_phase(name, layers, xs, ref, wdt, gate, backend="pallas",
              max_batch=MAX_BATCH, bursts=BURSTS,
              reorder_iters=REORDER_ITERS):
    """One single-device phase; returns the list of failed checks."""
    problems = []
    engine = Engine(backend=backend, activation="gelu", reorder=True,
                    reorder_iters=reorder_iters, gate=gate,
                    weight_dtype=wdt)
    t0 = time.perf_counter()
    plans = BucketedPlanSet.compile(layers, engine=engine,
                                    max_batch=max_batch)
    plans.warmup()
    setup_s = time.perf_counter() - t0
    check_plan(name, plans.base, backend, problems)
    rows, server = serve(plans, xs, bursts)
    line = check_served(name, rows, server, ref, TOL[wdt], problems)
    print(f"phase {name}: backend={plans.base.backend} "
          f"fused={plans.base.fused} "
          f"fallback={plans.base.fallback_reason!r} weight_dtype={wdt} "
          f"gate={gate} setup_s={setup_s!r} "
          f"(compile_s={plans.compile_s!r}) {line}", flush=True)
    if gate:
        print(f"phase {name}: {plans.base.measure_dynamic(xs[:8]).summary()}",
              flush=True)
    return problems


def run_sharded(layers, xs, ref, model=4, backend="pallas",
                max_batch=MAX_BATCH, bursts=BURSTS,
                reorder_iters=REORDER_ITERS):
    """``Mesh(model, 1)`` through the serving path, against the unsharded
    plan on the same rows; returns the list of failed checks."""
    name = f"mesh{model}x1"
    problems = []
    mesh = Mesh(model, 1)
    jm = mesh.jax_mesh()
    if jm is None or jm.devices.size != model:
        return [f"{name}: no {model}-device mesh (jax sees "
                f"{jax.device_count()} devices); the plan would run the "
                "sequential shard loop"]
    engine = Engine(backend=backend, activation="gelu", reorder=True,
                    reorder_iters=reorder_iters)
    t0 = time.perf_counter()
    base = engine.compile(layers)
    check_plan(f"{name} unsharded", base, backend, problems)
    y_base = np.asarray(base(xs))
    plans = BucketedPlanSet.compile(layers, engine=engine,
                                    max_batch=max_batch, mesh=mesh)
    plans.warmup()
    setup_s = time.perf_counter() - t0
    if not isinstance(plans.base, ShardedExecutionPlan):
        problems.append(f"{name}: not a sharded plan")
    y_probe = plans.plans[max_batch]._forward(xs[:max_batch])
    spans = len(y_probe.sharding.device_set)
    if spans != model:
        problems.append(f"{name}: output spans {spans} devices, "
                        f"want {model}")
    rows, server = serve(plans, xs, bursts)
    line = check_served(name, rows, server, y_base, TOL["f32"], problems)
    err_ref = float("nan")
    if not any(r is None for r in rows):
        err_ref = rel_err(np.stack(rows), ref)
    print(f"phase {name}: {plans.base.describe()}", flush=True)
    print(f"phase {name}: output_devices={spans} setup_s={setup_s!r} "
          f"vs unsharded plan: {line}; unsharded vs reference: "
          f"max_rel_err={rel_err(y_base, ref)!r}; sharded vs reference: "
          f"max_rel_err={err_ref!r}", flush=True)
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the Mesh(4, 1) sharded path and the "
                         "unsharded plan it is compared with")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); refusing "
              "to run on anything else", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {cache}; annealer C helper loaded: "
          f"{_iosim_c.available()}", flush=True)

    layers = make_layers(args.seed)
    xs = make_requests(args.seed, sum(BURSTS), SIZES[0])
    ref = reference(layers, xs)
    print(f"network: {' -> '.join(map(str, SIZES))} block {BLOCK} density "
          f"{DENSITY} gelu, {sum(l.nnz_blocks for l in layers)} nonzero "
          f"blocks, seed {args.seed}; {len(xs)} requests in bursts "
          f"{list(BURSTS)}", flush=True)

    problems = []
    if args.chips == 4:
        phases = [("mesh4x1", lambda: run_sharded(layers, xs, ref, model=4))]
    else:
        phases = [(name, lambda n=name, w=wdt, g=gate:
                   run_phase(n, layers, xs, ref, w, g))
                  for name, wdt, gate in PHASES]
    for name, run in phases:
        try:
            problems += run()
        except Exception:
            traceback.print_exc()
            problems.append(f"{name}: raised (traceback on stderr)")
    if problems:
        for p in problems:
            print(f"FAILED {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
