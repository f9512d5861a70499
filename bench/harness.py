"""One run of one cell: set-up, the measured window, the comparison with
the reference, the metrics and the result line.

The program is reached through its public entries only: ``repro.engine``,
``repro.serving`` and ``repro.sparse`` from ``<checkout>/src``.
"""

from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
import traceback
import types
from collections import Counter
from typing import List, Optional

from bench.spec import ROOT, load_cell, load_module, read_metrics

sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
from jax import monitoring  # noqa: E402

from bench import loadgen, network, peaks, tracing  # noqa: E402

LOADS = {"poisson": loadgen.OpenLoop, "closed": loadgen.ClosedLoop,
           "offline": loadgen.Offline}
# programs this process built (compiled, or loaded from the persistent
# cache), and how many of those the cache held
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compiles = {"built": 0, "cache_hits": 0}


def _count_compile(event: str, secs: float, **kw) -> None:
    if event == COMPILE_EVENT:
        _compiles["built"] += 1


def _count_cache_hit(event: str, **kw) -> None:
    if event == CACHE_HIT_EVENT:
        _compiles["cache_hits"] += 1


monitoring.register_event_duration_secs_listener(_count_compile)
monitoring.register_event_listener(_count_cache_hit)

SAMPLED_ROWS_MIN = 256


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _span_factory(trace: bool):
    return jax.profiler.TraceAnnotation if trace else loadgen.no_span


def _check(name: str, value, op: str, limit) -> dict:
    """``value op limit``; a value that could not be read (None) fails."""
    ok = value is not None and (value <= limit if op == "<="
                                else value >= limit)
    return {"name": name, "value": value, "op": op, "limit": limit,
            "ok": bool(ok)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             config_overrides: Optional[dict] = None,
             traffic_overrides: Optional[dict] = None):
    """Run ``workload`` once; returns ``(result, checks)``.

    ``require_chip=False`` and the overrides are for tests and for the
    readings of the control (``readings.py``); the command never sets them.
    """
    cell = load_cell(workload)
    config = {**cell.config, **(config_overrides or {})}
    traffic = {**cell.traffic, **(traffic_overrides or {})}
    ref_mod = load_module(config["reference"], cell.modules)
    net = network.hooks(config, cell.modules)

    devices = jax.devices()
    if require_chip:
        if devices[0].platform == "cpu":
            raise NoChip("JAX found no accelerator (platform 'cpu')")
        if len(devices) < cell.chips:
            raise NoChip(f"the cell asks for {cell.chips} chips and JAX "
                         f"sees {len(devices)}")
    devices = devices[:cell.chips]
    jax_start_s = time.perf_counter() - t_start

    from repro.cachedir import enable_compile_cache
    enable_compile_cache()
    # cache every program, however fast it compiles: later runs of a cell
    # then load each one
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    system = network.build(config, seed, net)
    plan_faults = network.plan_problems(system.plans, config)
    load = LOADS[traffic["kind"]](system, config, traffic, seed,
                                      span=_span_factory(trace))
    setup_s = time.perf_counter() - t_start
    phases = {"jax_start": jax_start_s, "weights": system.weights_s,
              "prune": system.prune_s,
              "engine_compile": system.engine_compile_s,
              "bucket_warmup": load.bucket_warmup_s,
              "traffic_warmup": getattr(load, "traffic_warmup_s", 0.0)}

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=options)
    setup_compiles = dict(_compiles)
    try:
        window = load.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles_in_window = _compiles["built"] - setup_compiles["built"]

    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    load.close()
    ws, bs = system.dense
    pool = load.pool
    del load, system
    gc.collect()

    summary = None
    if trace:
        try:
            summary = tracing.summarize(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    weights, nnz = ref_mod.prune(config, ws)
    xs = pool[window.sample_idx]
    err = None
    if len(xs):
        ref = ref_mod.forward(config, weights, bs, xs)
        err = ref_mod.max_err_over_absmax(window.sample_out, ref)

    checks = [
        _check("max_err_over_absmax", err, "<=",
               config["limits"]["max_err_over_absmax"]),
        _check("answers_lost", window.lost, "<=", 0),
        _check("plan_faults", len(plan_faults), "<=", 0),
        _check("batch_faults", len(window.problems), "<=", 0),
        _check("sampled_rows", len(xs), ">=",
               min(SAMPLED_ROWS_MIN, window.attempted)),
    ]
    for problem in plan_faults + window.problems:
        print(f"fault: {problem}", file=sys.stderr)

    run = types.SimpleNamespace(
        config=config, traffic=traffic, window=window, setup_s=setup_s,
        setup_phases=phases, trace=summary, device_kind=device["kind"],
        counts=net["counts"](config, nnz))
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, run)
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in metrics:
                print(f"metric {m['name']}: nothing to read in this run",
                      file=sys.stderr)

    result = {"correct": all(c["ok"] for c in checks),
              "attempted": int(window.attempted),
              "failed": int(window.failed),
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
        if summary.kernel_calls:
            # which side of the roofline binds each kernel call, by the count
            p = peaks.peak(device["kind"])
            result["kernel_bound_by"] = dict(Counter(
                run.counts.bound_by(rows, p)
                for rows, _ in summary.kernel_calls))
    result["setup_phases_s"] = phases
    result["programs_built"] = {
        "setup": setup_compiles["built"],
        "setup_cache_hits": setup_compiles["cache_hits"],
        "window": compiles_in_window}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result, checks


def main(argv: Optional[List[str]], t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} {c['op']} {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0
