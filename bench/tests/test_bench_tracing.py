"""The reduction from a profiler trace to busy time, kernel calls and idle
gaps, on a hand-made trace and on one recorded on a TPU v5e."""

import json
from pathlib import Path

import pytest

from bench import counts, peaks, tracing
from bench.tracing import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
K8 = ("%bsr_megakernel.1 = f32[8,1024]{1,0:T(8,128)} custom-call("
      "s32[66]{0:T(128)} %constant.16)")
K256 = K8.replace("f32[8,1024]", "f32[256,1024]")
BCAST = "%broadcast.1 = f32[66]{0:T(128)S(1)} broadcast(f32[] %c), dims={}"
RECORDED = Path(__file__).parent / "data" / "trace_small.json"


def _ev(plane, line, name, start_us, dur_us):
    return Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


def _hand_made():
    return [
        _ev(HOST, "python", "bench.submit", 0, 10),
        _ev(HOST, "collect", "bench.wait", 5, 995),
        _ev(HOST, "python", "XlaLinearize", 100, 200),
        _ev(HOST, "python", "bench.submit", 990, 10),
        _ev(DEV, "XLA Ops", BCAST, 300, 1),
        _ev(DEV, "XLA Ops", K8, 301, 20),
        _ev(DEV, "XLA Ops", K256, 600, 30),
        _ev(DEV, "XLA Ops", K256, 620, 30),        # overlaps the one before
        _ev(DEV, "XLA Ops", K8, 2000, 20),         # after the window
    ]


def test_busy_window_and_kernel_calls():
    s = tracing.reduce(_hand_made())
    assert s.window_s == pytest.approx(1000e-6)
    assert s.busy_s == pytest.approx((21 + 50) * 1e-6)
    assert sorted(s.kernel_calls) == pytest.approx(
        [(8, 20e-6), (256, 30e-6), (256, 30e-6)])
    ops = dict(s.device_ops)
    assert ops["bsr_megakernel f32[256,1024]"] == pytest.approx(60e-6)
    assert ops["broadcast f32[66]"] == pytest.approx(1e-6)


def test_idle_gaps_say_what_the_host_was_doing():
    s = tracing.reduce(_hand_made())
    gaps = s.idle_gaps
    assert [g for _, g in gaps] == pytest.approx(
        [350e-6, 300e-6, 279e-6], rel=1e-6)
    # 650..1000 us: only the collector's wait; 0..300 us: the transpose
    # (the shortest host event at its midpoint)
    assert gaps[0][0] == "bench.wait"
    assert gaps[1][0] == "XlaLinearize"


def test_a_trace_without_the_benchmark_spans_is_refused():
    with pytest.raises(RuntimeError, match="spans"):
        tracing.reduce([e for e in _hand_made() if e.plane == DEV])


def test_recorded_trace():
    rec = json.loads(RECORDED.read_text())
    events = [Event(*e) for e in rec["events"]]
    s = tracing.reduce(events)
    kernels = [e for e in events if e.plane == DEV
               and e.name.startswith("%bsr_megakernel")]
    assert len(s.kernel_calls) == len(kernels) > 0
    assert 0 < s.busy_s < s.window_s
    assert sum(d for _, d in s.device_ops) >= s.busy_s * (1 - 1e-9)
    # the kernel's share of its roofline on the recorded calls is a share
    bert = counts.SparseFFN(1024, 4096, 1024, 128, 52, "bf16")
    v5e = peaks.peak(rec["device"]["kind"])
    share = sum(bert.bound_s(r, v5e) for r, _ in s.kernel_calls) / s.kernel_s
    assert 0 < share < 1
