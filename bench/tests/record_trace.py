#!/usr/bin/env python3
"""Record the small trace that ``test_bench_tracing.py`` reduces.

    python3 bench/tests/record_trace.py --workload bert-large-ffn.steady \
        --out bench/tests/data/trace_small.json

Runs the cell once, traced, for a second on the chip and writes the first
``--keep-ms`` of its window, as the reduction reads it, to ``--out``.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracing  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--keep-ms", type=float, default=60.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    summarize = tracing.summarize
    kept = {}

    def recording(tdir):
        events = tracing.load_events(tracing.find_trace(tdir))
        spans = [e for e in events if e.name.startswith(tracing.BENCH_SPAN)]
        t0 = min(e.start_ns for e in spans)
        t1 = t0 + args.keep_ms * 1e6
        kept["events"] = [
            [e.plane, e.line, e.name[:120], e.start_ns, e.dur_ns]
            for e in events if t0 <= e.start_ns and e.end_ns <= t1]
        return summarize(tdir)

    tracing.summarize = recording
    result, _ = harness.run_cell(args.workload, args.seed, 1.0, True,
                                 time.perf_counter())
    Path(args.out).write_text(json.dumps({"workload": args.workload,
                               "device": result["device"],
                               "events": kept["events"]}))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
