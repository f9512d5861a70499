"""From a profiler trace to the device's busy time, the kernel's calls and
the longest idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; JAX's own
``ProfileData`` reads it.  What is used of it:

  * the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane: every operation
    the chip ran, named by its HLO text;
  * the ``/host:CPU`` plane: the benchmark's own spans (``bench.*``) and the
    runtime's host events, on the same clock.

The traced window runs from the start of the first ``bench.*`` span to the
end of the last one.  Busy time is the union of the device's operations
in it, averaged over the chips.  The megakernel's calls are the
operations named ``%bsr_megakernel...``; the rows of a call are read from
its output's shape, so the count of its work needs nothing from the
program.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
BENCH_SPAN = "bench."
KERNEL = re.compile(r"^%bsr_megakernel(?:\.\d+)? = \(?\w+\[(\d+),(\d+)\]")
OP_NAME = re.compile(r"^%([\w\-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])")
TOP = 10


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(path: str) -> List[Event]:
    """Device operations and host events of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            out += [Event(plane.name, line.name, e.name, e.start_ns,
                          e.duration_ns) for e in line.events]
    return out


def find_trace(tdir: str) -> str:
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {tdir}, found {files}")
    return files[0]


def _merge(intervals: Sequence[Tuple[float, float]]):
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def op_name(hlo: str) -> str:
    """``%bsr_megakernel.1 = f32[8,1024]{...} custom-call(...)`` ->
    ``bsr_megakernel f32[8,1024]``."""
    m = OP_NAME.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:80]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_calls: List[Tuple[int, float]]     # (rows, seconds) per call
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def kernel_s(self) -> float:
        return sum(s for _, s in self.kernel_calls)

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


class _HostIndex:
    """Host events per thread, to say what the host was doing at an
    instant: the shortest event that spans it."""

    def __init__(self, events: Sequence[Event]):
        lines: Dict[str, List[Event]] = {}
        for e in events:
            lines.setdefault(e.line, []).append(e)
        self.lines = []
        for evs in lines.values():
            evs.sort(key=lambda e: e.start_ns)
            self.lines.append(([e.start_ns for e in evs], evs))

    def at(self, t: float) -> str:
        best: Optional[Event] = None
        for starts, evs in self.lines:
            i = bisect.bisect_right(starts, t) - 1
            for e in evs[max(0, i - 64):i + 1][::-1]:
                if e.end_ns >= t:
                    if best is None or e.dur_ns < best.dur_ns:
                        best = e
                    break
        return best.name if best is not None else "no host event"


def reduce(events: Sequence[Event]) -> Summary:
    host = [e for e in events if e.plane == HOST_PLANE]
    spans = [e for e in host if e.name.startswith(BENCH_SPAN)]
    if not spans:
        raise RuntimeError("the trace holds none of the benchmark's spans")
    t0 = min(e.start_ns for e in spans)
    t1 = max(e.end_ns for e in spans)
    devices: Dict[str, List[Event]] = {}
    for e in events:
        if e.plane != HOST_PLANE and t0 <= e.start_ns and e.end_ns <= t1:
            devices.setdefault(e.plane, []).append(e)
    if not devices:
        raise RuntimeError("no operation ran on the device in the window")

    busy, gaps, by_op, calls = [], [], {}, []
    for plane, ops in devices.items():
        merged = _merge([(e.start_ns, e.end_ns) for e in ops])
        busy.append(sum(e - s for s, e in merged))
        edges = [t0] + [x for m in merged for x in m] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for e in ops:
            name = op_name(e.name)
            by_op[name] = by_op.get(name, 0.0) + e.dur_ns / 1e9
            m = KERNEL.match(e.name)
            if m:
                calls.append((int(m.group(1)), e.dur_ns / 1e9))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    index = _HostIndex(host)
    return Summary(
        window_s=(t1 - t0) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        kernel_calls=calls,
        device_ops=sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP],
        idle_gaps=[(index.at((s + e) / 2), (e - s) / 1e9)
                   for s, e in longest])


def summarize(tdir: str) -> Summary:
    return reduce(load_events(find_trace(tdir)))
