#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate whose p99 stays
within the configuration's SLO with nothing refused and no growing backlog.

    python3 bench/sweep.py --workload bert-large-ffn.steady \
        --rates 2000,4000,8000 --seconds 10 --seed 7

Builds the cell's system once, then serves the cell's mix at each rate in
turn, each on a fresh server.  One JSON line per rate on standard output.
Not part of a benchmark run: the rate it finds is written into the cell's
traffic file.
"""

import argparse
import json
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402,F401  (puts the program on the path)
from bench.spec import load_cell, load_reader  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform == "cpu":
        print("sweep: no accelerator", file=sys.stderr)
        return 2
    from repro.cachedir import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import loadgen, network

    cell = load_cell(args.workload)
    slo_ms = cell.config["slo_ms"]
    p50, p99 = load_reader("latency_p50_ms"), load_reader("latency_p99_ms")
    system = network.build(cell.config, args.seed,
                           network.hooks(cell.config, cell.modules))
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = {**cell.traffic, "rate_rps": rate}
        load = loadgen.OpenLoop(system, cell.config, traffic, args.seed)
        w = load.run(args.seconds)
        depth = load.server.metrics.max_queue_depth
        load.close()
        # a refused or lost request is infinitely late: the p99 reads None
        # where more than 1% are
        run = types.SimpleNamespace(window=w)
        lat = w.latency_s[np.isfinite(w.latency_s)] * 1e3
        fifth = max(1, len(lat) // 5)
        c = w.counters
        row = {
            "rate_rps": rate, "requests": int(w.attempted),
            "failed": int(w.failed),
            "p50_ms": p50(run),
            "p99_ms": p99(run),
            "first_fifth_p50_ms": float(np.median(lat[:fifth])),
            "last_fifth_p50_ms": float(np.median(lat[-fifth:])),
            "gen_lag_p99_ms": float(np.percentile(w.gen_lag_s, 99) * 1e3),
            "batch_rows_mean": c["served"] / max(1, c["batches"]),
            "form_wait_mean_ms":
                1e3 * c["form_wait_s"] / max(1, c["form_wait_n"]),
            "exec_mean_ms": 1e3 * c["exec_s"] / max(1, c["exec_n"]),
            "max_queue_depth": int(depth),
        }
        row["holds_slo"] = bool(
            row["p99_ms"] is not None and row["p99_ms"] <= slo_ms
            and not row["failed"]
            and row["last_fifth_p50_ms"] <= 2 * row["first_fifth_p50_ms"])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
