"""Megakernel vs. layer-by-layer dispatch latency + annealer delta speedup.

    PYTHONPATH=src python benchmarks/bench_engine.py [--density 0.2] [--batch 32]

Measures, for the same pruned multi-layer FFNN and the same connection
schedule:

  * layered: one dispatch per layer (the PR-1 call pattern — per-layer
    ``pallas_call``/jnp boundaries, hidden state through HBM each boundary);
  * fused: the flat cross-layer schedule from ``Engine.compile`` — the
    megakernel on pallas/interpret, one segment pass on jnp;
  * reorder: per-proposal cost of the annealer's windowed incremental I/O
    delta evaluation (``core.iosim.IncrementalSimulator``) vs a full O(W)
    ``simulate()`` per proposal, on the same proposal stream;

and reports simulated tile I/O next to the Theorem-1 bounds plus the fused
plan's cross-layer savings.  Results are printed AND written to a
machine-readable ``BENCH_engine.json`` so the perf trajectory is tracked
across PRs (CI uploads it as an artifact).

On CPU hosts the latency comparison runs on the ``jnp`` backend (the Pallas
interpret mode is a correctness path, not a perf path); on TPU pass
``--backend pallas``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cachedir import enable_compile_cache
from repro.core.iosim import IncrementalSimulator, simulate
from repro.core import _iosim_c
from repro.engine import Engine, make_forward
from repro.sparse import prune_dense_stack


def timeit(fn, x, iters: int, warmup: int = 3) -> float:
    """Median wall time per call (seconds)."""
    for _ in range(warmup):
        fn(x).block_until_ready()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_reorder(net, order, M: int, iters: int, seed: int = 0) -> dict:
    """Per-proposal cost: windowed incremental delta vs full re-simulation.

    Replays the identical proposal stream through both evaluators (the delta
    totals are exact, so both see the same accept/reject costs)."""
    rng = np.random.default_rng(seed)
    src32 = np.ascontiguousarray(net.src, dtype=np.int32)
    dst32 = np.ascontiguousarray(net.dst, dtype=np.int32)
    avg_in = net.W / max(1, net.N - net.I)
    ws = max(1, int(round(4 * avg_in)))
    cur = np.ascontiguousarray(order, dtype=np.int64).copy()
    moves = []
    for _ in range(iters):
        i = int(rng.integers(0, net.W))
        w = int(rng.integers(0, ws))
        d = 0 if rng.random() < 0.5 else 1
        cand = cur.copy()
        if not _iosim_c.propose_move_c(cand, src32, dst32, i, w, d):
            from repro.core.reorder import _apply_move
            cand = np.array(_apply_move(cur.tolist(), net.src.tolist(),
                                        net.dst.tolist(), i, w, d), np.int64)
        moves.append(cand)

    sim = IncrementalSimulator(net, cur, M)
    t0 = time.perf_counter()
    delta_totals = [sim.propose(c) for c in moves]
    t_delta = (time.perf_counter() - t0) / len(moves)
    t0 = time.perf_counter()
    full_totals = [simulate(net, c, M, "min").total for c in moves]
    t_full = (time.perf_counter() - t0) / len(moves)
    assert delta_totals == full_totals, "delta evaluation diverged from full"
    return {
        "proposals": len(moves),
        "W_blocks": int(net.W),
        "delta_ms_per_proposal": 1e3 * t_delta,
        "full_ms_per_proposal": 1e3 * t_full,
        "speedup": t_full / max(t_delta, 1e-12),
    }


def bench_dynamic_sparsity(backend: str, batch: int, iters: int) -> dict:
    """Occupancy-gating sweep: ReLU nets at varying *dynamic* sparsity.

    The same pruned net is run with a growing fraction of its hidden tiles
    forced dead (bias ``-10`` drives every pre-activation in the tile below
    zero, so ReLU zeroes it for any input in range) — static structure and
    schedule identical across the sweep, only the runtime activation
    sparsity changes.  For each point: assert the gated forward is
    bit-identical to the ungated one, measure dynamic vs static weight-block
    reads, and time both forwards.
    """
    rng = np.random.default_rng(1)
    sizes = [256, 512, 512, 256]
    block = 64
    ws = [rng.standard_normal((sizes[i], sizes[i + 1])).astype(np.float32)
          * 0.03 for i in range(len(sizes) - 1)]
    bs = [np.zeros(s, np.float32) for s in sizes[1:]]
    base_layers = prune_dense_stack(ws, bs, density=0.3,
                                    block_m=block, block_n=block)
    x = jnp.asarray(rng.standard_normal((batch, sizes[0])), jnp.float32)

    sweep = []
    for frac in (0.0, 0.25, 0.5, 0.75):
        layers = []
        for k, lay in enumerate(base_layers):
            if k < len(base_layers) - 1:
                kill = int(frac * lay.grid_out)
                bias = np.array(lay.bias, np.float32)
                bias.reshape(lay.grid_out, lay.block_n)[:kill] = -10.0
                lay = dataclasses.replace(lay, bias=bias)
            layers.append(lay)
        gated = Engine(backend=backend, activation="relu",
                       gate=True).compile(layers)
        ungated = Engine(backend=backend,
                         activation="relu").compile(layers)
        np.testing.assert_array_equal(np.asarray(gated(x)),
                                      np.asarray(ungated(x)))
        rep = gated.measure_dynamic(x)
        if frac >= 0.5:
            assert rep.dynamic_total < rep.static_total, (
                f"gating read no fewer blocks than the static schedule at "
                f"{frac:.0%} dead tiles: {rep.summary()}"
            )
        t_gated = timeit(gated, x, iters)
        t_ungated = timeit(ungated, x, iters)
        print(f"  gate sweep frac={frac:.2f}: read "
              f"{rep.dynamic_total}/{rep.static_total} blocks "
              f"({100 * rep.read_fraction:.0f}%), "
              f"gated {1e3*t_gated:.2f} ms vs ungated {1e3*t_ungated:.2f} ms")
        sweep.append({
            "dead_tile_fraction": frac,
            "static_blocks": rep.static_total,
            "dynamic_blocks": rep.dynamic_total,
            "blocks_skipped": rep.blocks_skipped,
            "read_fraction": rep.read_fraction,
            "latency_ms_gated": 1e3 * t_gated,
            "latency_ms_ungated": 1e3 * t_ungated,
        })
    return {
        "net": {"sizes": sizes, "density": 0.3, "block": block,
                "batch": batch},
        "sweep": sweep,
    }


def bench_weight_stream(layers, backend: str, x, iters: int,
                        reorder_iters: int) -> dict:
    """Quantized weight-stream sweep: bytes moved, latency, and error.

    The SAME schedule runs at f32/bf16/fp8 weight storage — tile counts and
    Theorem-1 bounds are dtype-invariant, only the bytes per streamed block
    shrink.  Asserts the acceptance floor: bf16 <= 0.55x the f32 weight
    bytes (>= 1.8x reduction), fp8 >= 3.5x, with bounded output error.
    """
    from repro.kernels.ops import FP8_DTYPE

    dtypes = ["f32", "bf16"] + (["fp8"] if FP8_DTYPE is not None else [])
    max_rel_err = {"f32": 0.0, "bf16": 1e-2, "fp8": 1e-1}
    min_reduction = {"bf16": 1.8, "fp8": 3.5}
    sweep = []
    y_ref = None
    f32_bytes = 0
    for wdt in dtypes:
        plan = Engine(backend=backend, activation="relu", reorder=True,
                      reorder_iters=reorder_iters,
                      weight_dtype=wdt).compile(layers)
        y = np.asarray(plan(x), np.float32)
        if wdt == "f32":
            y_ref = y
            f32_bytes = plan.io.weight_stream_bytes
        rel = float(np.max(np.abs(y - y_ref))
                    / max(1e-9, np.max(np.abs(y_ref))))
        assert rel <= max_rel_err[wdt], (
            f"{wdt} output error {rel:.4f} exceeds {max_rel_err[wdt]}")
        reduction = f32_bytes / plan.io.weight_stream_bytes
        if wdt in min_reduction:
            assert reduction >= min_reduction[wdt], (
                f"{wdt} weight-stream bytes shrank only {reduction:.2f}x "
                f"(need >= {min_reduction[wdt]}x)")
        t = timeit(plan, x, iters)
        print(f"  weight stream {wdt:>4}: "
              f"{plan.io.weight_stream_bytes:>9} B/forward "
              f"({reduction:.2f}x vs f32), {1e3*t:.2f} ms/batch, "
              f"max rel err {rel:.2e}")
        sweep.append({
            "weight_dtype": wdt,
            "weight_bytes_streamed": plan.io.weight_bytes_streamed,
            "scale_bytes_streamed": plan.io.scale_bytes_streamed,
            "weight_stream_bytes": plan.io.weight_stream_bytes,
            "bytes_reduction_vs_f32": reduction,
            "latency_ms": 1e3 * t,
            "max_rel_err_vs_f32": rel,
        })
    return {"sweep": sweep, "dtypes": dtypes}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[768, 1536, 1536, 1536, 1536, 768])
    ap.add_argument("--density", type=float, default=0.2)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--reorder-iters", type=int, default=300,
                    help="annealing budget for the compiled plan AND the "
                         "proposal count of the delta-vs-full comparison")
    ap.add_argument("--reorder-block", type=int, default=16,
                    help="tile size for the delta-evaluation benchmark DAG "
                         "(finer tiles -> the 10k+-block regime the "
                         "incremental evaluator targets)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "interpret", "jnp"))
    ap.add_argument("--out", default="BENCH_engine.json",
                    help="where to write the machine-readable results")
    args = ap.parse_args()
    enable_compile_cache()

    rng = np.random.default_rng(0)
    sizes = args.sizes
    ws = [rng.standard_normal((sizes[i], sizes[i + 1])).astype(np.float32) * 0.03
          for i in range(len(sizes) - 1)]
    bs = [np.zeros(s, np.float32) for s in sizes[1:]]
    layers = prune_dense_stack(ws, bs, density=args.density,
                               block_m=args.block, block_n=args.block)

    engine = Engine(backend=args.backend, activation="relu", reorder=True,
                    reorder_iters=args.reorder_iters)
    t0 = time.time()
    plan = engine.compile(layers)
    compile_s = time.time() - t0
    print(f"compile: {compile_s:.2f}s — {plan.describe()}")
    assert plan.fused, "expected the fused flat-schedule plan"

    # the layered baseline: same layers, same schedule arrays, same backend,
    # but one *jitted dispatch per layer* — the PR-1 call pattern the
    # megakernel replaces (hidden state crosses HBM at every boundary)
    per_layer = [
        make_forward([lay], [sch], [act], plan.backend)
        for lay, sch, act in zip(plan.layers, plan.schedules,
                                 plan.activations)
    ]

    def layered(h):
        for fn in per_layer:
            h = fn(h)
        return h

    x = jnp.asarray(rng.standard_normal((args.batch, sizes[0])), jnp.float32)
    t_layered = timeit(layered, x, args.iters)
    t_fused = timeit(plan, x, args.iters)
    speedup = t_layered / max(t_fused, 1e-12)

    np.testing.assert_allclose(np.asarray(layered(x)),
                               np.asarray(plan(x)), rtol=1e-5, atol=1e-5)

    print(f"backend={plan.backend} batch={args.batch} "
          f"net={'x'.join(map(str, sizes))} density={args.density}")
    print(f"  layered (per-layer dispatch): {1e3*t_layered:8.2f} ms/batch")
    print(f"  fused   (megakernel path):    {1e3*t_fused:8.2f} ms/batch "
          f"({speedup:.2f}x)")

    # delta evaluation: benchmark on a finer-grained block DAG of the same
    # net — the 10k+-block regime "CR at scale" targets
    from repro.core.blocksparse import to_block_ffnn
    from repro.core.graph import drop_isolated
    fine_layers = prune_dense_stack(ws, bs, density=args.density,
                                    block_m=args.reorder_block,
                                    block_n=args.reorder_block)
    fine_net = to_block_ffnn(fine_layers).net
    fine_order = fine_net.theorem1_order()
    reorder_stats = bench_reorder(fine_net, fine_order, engine.M_tiles,
                                  iters=args.reorder_iters)
    print(f"  reorder: {reorder_stats['delta_ms_per_proposal']:.3f} ms/proposal "
          f"(delta) vs {reorder_stats['full_ms_per_proposal']:.3f} ms (full) "
          f"-> {reorder_stats['speedup']:.1f}x over "
          f"{reorder_stats['proposals']} proposals, "
          f"W={reorder_stats['W_blocks']} blocks")

    print("dynamic-sparsity gating sweep (ReLU, forced-dead hidden tiles):")
    dyn_stats = bench_dynamic_sparsity(plan.backend, args.batch, args.iters)

    print("quantized weight-stream sweep (same schedule, narrower storage):")
    quant_stats = bench_weight_stream(layers, plan.backend, x, args.iters,
                                      reorder_iters=args.reorder_iters)

    io = plan.io
    result = {
        "net": {
            "sizes": sizes,
            "density": args.density,
            "block": args.block,
            "batch": args.batch,
            "nnz_blocks": int(sum(l.nnz_blocks for l in layers)),
        },
        "backend": plan.backend,
        "fused": plan.fused,
        "compile_s": compile_s,
        "latency_ms": {
            "layered": 1e3 * t_layered,
            "fused": 1e3 * t_fused,
        },
        "fused_vs_layered_speedup": speedup,
        "io": {
            "simulated_reads": io.simulated.reads,
            "simulated_writes": io.simulated.writes,
            "simulated_total": io.simulated.total,
            "bound_total_lo": io.bounds.total_lo,
            "bound_total_hi": io.bounds.total_hi,
            "optimality_ratio": io.optimality_ratio,
            "within_bounds": io.within_bounds,
            "layered_total": io.layered_total,
            "cross_layer_savings": io.cross_layer_savings,
            "hidden_tiles_kept": io.hidden_tiles_kept,
            "hidden_bytes_kept_per_row": io.hidden_bytes_kept_per_row,
        },
        "reorder": reorder_stats,
        "dynamic_sparsity": dyn_stats,
        "weight_stream": quant_stats,
        "env": {
            "jax": jax.__version__,
            "jax_backend": jax.default_backend(),
            "python": platform.python_version(),
            # device count + mesh shape make the perf trajectory comparable
            # across environments (single vs forced-multi-device hosts)
            "devices": jax.device_count(),
            "mesh": {"model": 1, "data": 1},
        },
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
