#!/usr/bin/env python3
"""Readings that set a cell's limit: the program over many seeds, and the
control, the same cell with fp8 weights in place of the configuration's
bf16 (the program's own lower-precision path).

    python3 bench/readings.py --workload bert-large-ffn.steady \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 3

Each reading is one full run of the cell (set-up, a short window at the
cell's own load, the comparison with the reference) in this one process.
One JSON line per reading on standard output.  Not part of a benchmark
run.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

CONTROL = {"weight_dtype": "fp8"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    runs = [(int(s), "program") for s in args.seeds.split(",") if s]
    runs += [(int(s), "control_fp8") for s in args.control_seeds.split(",")
             if s]
    for seed, side in runs:
        try:
            result, _ = harness.run_cell(
                args.workload, seed, args.seconds, False, time.perf_counter(),
                config_overrides=CONTROL if side != "program" else None)
            row = {"seed": seed, "side": side, "correct": result["correct"],
                   "checks": result["checks"], "metrics": result["metrics"]}
        except harness.NoChip as e:
            print(f"readings: {e}", file=sys.stderr)
            return 2
        except Exception as e:   # a control that crashes gives no number
            row = {"seed": seed, "side": side, "error": repr(e)}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
