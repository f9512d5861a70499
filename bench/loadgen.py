"""The one traffic generator.  A mix is a data file, ``traffic/<name>.json``,
whose ``kind`` picks one of three loads and whose other keys are its
parameters:

  * ``poisson`` -- an open loop of single-row requests through
    ``SparseServer.submit``/``wait`` at ``rate_rps``.  Each request is timed
    from the moment it was due to the moment its answer is in the client's
    hand, and the generator's own lateness is kept;
  * ``closed`` -- ``outstanding`` single-row requests kept in flight
    through the same server: each answer taken back is replaced by a new
    request;
  * ``offline`` -- back-to-back calls of ``BucketedPlanSet.__call__`` with
    ``rows_per_call`` rows each.

Inputs are dense N(0, 1) rows (an FFN's input is a normalised hidden state
with no dead tiles), made once from the seed and drawn from a pool.  Every
seed gives the same amount of work: the open loop's gaps are the same set
of exponential quantiles, shuffled by the seed.

Each load sets itself up (server started, shapes warmed, code paths run
once) when it is made; ``run(seconds)`` is the measured window.  A
seeded reservoir keeps a sample of the answers for the comparison with the
reference.
"""

from __future__ import annotations

import contextlib
import dataclasses
import random
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.serving import SparseServer

NULL_SPAN = contextlib.nullcontext()
SAMPLE_ROWS = 2048
CALL_SAMPLE_ROWS = 64        # answers sampled from each offline call
RESULT_WAIT_S = 60.0
# A client that waits on its answer keeps it from the server's eviction;
# the collector polls instead.  So the server keeps every finished answer
# until it is taken: with the program's default of 4096, a stall of the
# machine of a second or two at 4800 req/s puts the collector that far
# behind, and the evicted answers would read as lost.
RESULT_CAPACITY = 1 << 22
POLL_S = 1e-3              # the longest the collector waits on one answer


def no_span(name: str):
    return NULL_SPAN


@dataclasses.dataclass
class Window:
    """What one measured window produced."""

    seconds: float              # the window's length by the host clock
    attempted: int              # requests (rows, offline) started in it
    failed: int                 # refused by the server, or lost
    lost: int                   # admitted and never answered
    rows: int                   # rows whose answer came back in the window
    sample_idx: np.ndarray      # pool rows of the sampled answers
    sample_out: np.ndarray      # the sampled answers
    latency_s: Optional[np.ndarray] = None   # per request, open loop
    gen_lag_s: Optional[np.ndarray] = None   # send time minus due time
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    problems: List[str] = dataclasses.field(default_factory=list)


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from ``seed``.  An item is ``per`` answers and their pool rows."""

    def __init__(self, k: int, seed: int, width: int, per: int = 1):
        self.k = k
        self.rnd = random.Random(seed)
        self.seen = 0
        self.idx = np.full((k, per), -1, np.int64)
        self.out = np.zeros((k, per, width), np.float32)

    def offer(self, pool_idx, y) -> None:
        i = self.seen
        self.seen += 1
        slot = i if i < self.k else self.rnd.randrange(i + 1)
        if slot < self.k:
            self.idx[slot] = pool_idx
            self.out[slot] = y

    def taken(self):
        n = min(self.seen, self.k)
        return (self.idx[:n].reshape(-1).copy(),
                self.out[:n].reshape(-1, self.out.shape[-1]).copy())


def input_pool(seed: int, rows: int, width: int) -> np.ndarray:
    return np.random.default_rng([seed, 1]).standard_normal(
        (rows, width), dtype=np.float32)


def poisson_due(rate_rps: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in ``(0, seconds]`` of ``round(rate * seconds)`` arrivals.

    The gaps are the exponential distribution's quantiles at ``(i + 1/2)/n``
    in an order drawn from ``seed``, scaled so the last one falls on
    ``seconds``: Poisson-like bursts, and the same work from every seed.
    """
    n = max(1, int(round(rate_rps * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = np.random.default_rng([seed, 2]).permutation(gaps)
    due = np.cumsum(gaps)
    return due * (seconds / due[-1])


def _server_counters(server: SparseServer) -> Dict[str, float]:
    m = server.metrics
    return {"served": m.served, "batches": m.batches,
            "batch_failures": m.batch_failures,
            "failed_requests": m.failed_requests,
            "degraded_batches": m.degraded_batches,
            "rejected": m.rejected,
            "form_wait_s": m.form_wait_s.total,
            "form_wait_n": m.form_wait_s.count,
            "exec_s": m.exec_s.total, "exec_n": m.exec_s.count}


def _delta(after: Dict[str, float], before: Dict[str, float]):
    return {k: after[k] - before[k] for k in after}


class Outstanding:
    """Requests in flight, oldest first, each collected at the moment it is
    seen done.  ``take`` waits at most ``POLL_S`` for the oldest; whenever
    the server has finished a batch since the last look, it then looks at
    every other one too.  So an answer that completes before older ones (a
    batch of another bucket, on another executor) is taken when it is
    done, not when the oldest is: the collector adds no head-of-line wait."""

    def __init__(self, server, span: Callable):
        self.server, self.span = server, span
        self.pending: Dict[int, int] = {}        # rid -> request index
        self.seen = self._batches_finished()

    def __len__(self) -> int:
        return len(self.pending)

    def add(self, j: int, rid: int) -> None:
        self.pending[rid] = j

    def _batches_finished(self) -> int:
        # counted in the same locked section that completes the batch's
        # slots, after them: a batch counted here has its answers done
        m = self.server.metrics
        return m.batches + m.batch_failures

    def take(self, handle: Callable) -> None:
        """``handle(j, answer, t)`` for each request found done at ``t``;
        the answer is None for one that will never come (a failed batch,
        an evicted result)."""
        server, pending = self.server, self.pending
        head = next(iter(pending))
        with self.span("bench.wait"):
            y = server.wait(head, POLL_S)
        t = time.perf_counter()
        if y is not None:
            handle(pending.pop(head), y, t)
        finished = self._batches_finished()
        if finished == self.seen:
            return
        self.seen = finished
        for rid in list(pending):
            state = server.status(rid)
            if state != "pending":
                y = server.wait(rid, 0.0) if state == "done" else None
                handle(pending.pop(rid), y, t)


class _Served:
    """Shared by the server-driven mixes: the server with the program's
    defaults, except what the configuration fixes and the benchmark's own
    ``result_capacity`` (``RESULT_CAPACITY``)."""

    def __init__(self, system, config: dict, traffic: dict, seed: int,
                 span: Callable = no_span):
        self.plans = system.plans
        self.traffic = traffic
        self.seed = seed
        self.span = span
        self.pool = input_pool(seed, traffic["pool_rows"], self.plans.n_in)
        t0 = time.perf_counter()
        self.plans.warmup()
        self.bucket_warmup_s = time.perf_counter() - t0
        self.server = SparseServer(self.plans, slo_ms=config["slo_ms"],
                                   max_batch=config["max_batch"],
                                   result_capacity=RESULT_CAPACITY).start()

    def close(self) -> None:
        self.server.shutdown(drain=True)

    def _window(self, seconds, attempted, refused, lost, rows, sample,
                before, **kw) -> Window:
        counters = _delta(_server_counters(self.server), before)
        problems = [f"{k} = {counters[k]}" for k in
                    ("batch_failures", "degraded_batches") if counters[k]]
        idx, out = sample.taken()
        return Window(seconds=seconds, attempted=attempted,
                      failed=refused + lost, lost=lost, rows=rows,
                      sample_idx=idx, sample_out=out, counters=counters,
                      problems=problems, **kw)

class OpenLoop(_Served):
    """``poisson``: single-row requests sent on a schedule, whatever the
    server does."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        t0 = time.perf_counter()
        self._loop(poisson_due(self.traffic["rate_rps"],
                               self.traffic["warmup_s"], self.seed + 1),
                   Reservoir(1, self.seed, self.plans.n_out))
        self.traffic_warmup_s = time.perf_counter() - t0

    def run(self, seconds: float) -> Window:
        due = poisson_due(self.traffic["rate_rps"], seconds, self.seed)
        sample = Reservoir(SAMPLE_ROWS, self.seed, self.plans.n_out)
        before = _server_counters(self.server)
        lat, lag, tally, t_window = self._loop(due, sample)
        return self._window(t_window, len(due), tally["refused"],
                            tally["lost"], int(np.sum(due + lat <= t_window)),
                            sample, before, latency_s=lat, gen_lag_s=lag)

    def _loop(self, due: np.ndarray, sample: Reservoir):
        n = len(due)
        pick = np.random.default_rng([self.seed, 3, n]).integers(
            len(self.pool), size=n)
        sent = np.zeros(n)
        got = np.full(n, np.inf)         # never answered: infinitely late
        tally = {"done": 0, "refused": 0, "lost": 0}
        server, span, pool = self.server, self.span, self.pool
        inbox: deque = deque()           # (j, rid) as sent; rid None: refused
        out = Outstanding(server, span)
        give_up = [np.inf]

        def handle(j, y, t):
            tally["done"] += 1
            if y is None:
                tally["lost"] += 1
            else:
                got[j] = t
                sample.offer(int(pick[j]), y)

        def collect():
            while tally["done"] < n and time.perf_counter() < give_up[0]:
                while inbox:
                    j, rid = inbox.popleft()
                    if rid is None:
                        tally["done"] += 1
                        tally["refused"] += 1
                    else:
                        out.add(j, rid)
                if out:
                    out.take(handle)
                else:
                    time.sleep(1e-4)

        collector = threading.Thread(target=collect, name="bench-collect",
                                     daemon=True)
        collector.start()
        t0 = time.perf_counter() + 1e-3
        for j in range(n):
            wait = t0 + due[j] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[j] = time.perf_counter()
            with span("bench.submit"):
                rid = server.submit(pool[pick[j]])
            inbox.append((j, rid))
        t_window = max(time.perf_counter(), t0 + due[-1]) - t0
        give_up[0] = time.perf_counter() + RESULT_WAIT_S
        collector.join(RESULT_WAIT_S + 10.0)
        # admitted and not answered, also those the collector never reached
        tally["lost"] = n - tally["refused"] - int(np.isfinite(got).sum())
        return got - (t0 + due), sent - (t0 + due), tally, t_window


class ClosedLoop(_Served):
    """``closed``: ``outstanding`` requests always in flight."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        t0 = time.perf_counter()
        self._loop(self.traffic["warmup_s"],
                   Reservoir(1, self.seed, self.plans.n_out))
        self.traffic_warmup_s = time.perf_counter() - t0

    def run(self, seconds: float) -> Window:
        sample = Reservoir(SAMPLE_ROWS, self.seed, self.plans.n_out)
        before = _server_counters(self.server)
        c = self._loop(seconds, sample)
        return self._window(seconds, c["sent"], c["refused"], c["lost"],
                            c["rows"], sample, before)

    def _loop(self, seconds: float, sample: Reservoir):
        k = self.traffic["outstanding"]
        server, span, pool = self.server, self.span, self.pool
        order = np.random.default_rng([self.seed, 4]).permutation(len(pool))
        out = Outstanding(server, span)
        count = {"sent": 0, "refused": 0, "lost": 0, "rows": 0}
        t0 = time.perf_counter()
        t_end = t0 + seconds

        def send():
            j = count["sent"]
            count["sent"] += 1
            with span("bench.submit"):
                rid = server.submit(pool[order[j % len(order)]])
            if rid is None:
                count["refused"] += 1
            else:
                out.add(j, rid)

        def handle(j, y, t):
            if y is None:
                count["lost"] += 1
                return
            if t <= t_end:
                count["rows"] += 1
            sample.offer(int(order[j % len(order)]), y)

        for _ in range(k):
            send()
        while out and time.perf_counter() < t_end + RESULT_WAIT_S:
            out.take(handle)
            if time.perf_counter() < t_end:
                for _ in range(k - len(out)):
                    send()
        count["lost"] += len(out)          # never answered
        return count


class Offline:
    """``offline``: the batch entry called back to back."""

    def __init__(self, system, config: dict, traffic: dict, seed: int,
                 span: Callable = no_span):
        self.plans = system.plans
        self.seed = seed
        self.span = span
        n = traffic["rows_per_call"]
        self.pool = input_pool(seed, n * traffic["pool_calls"],
                               self.plans.n_in)
        self.calls = [self.pool[i:i + n] for i in range(0, len(self.pool), n)]
        t0 = time.perf_counter()
        self.plans(self.calls[0])            # the only shape this mix uses
        self.bucket_warmup_s = time.perf_counter() - t0

    def close(self) -> None:
        pass

    def run(self, seconds: float) -> Window:
        n = len(self.calls[0])
        per = min(CALL_SAMPLE_ROWS, n)
        rnd = random.Random(self.seed)
        sample = Reservoir(SAMPLE_ROWS // per, self.seed, self.plans.n_out,
                           per=per)
        rows = calls = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            c = calls % len(self.calls)
            with self.span("bench.plan_call"):
                y = self.plans(self.calls[c])
            calls += 1
            rows += len(y)
            at = np.asarray(rnd.sample(range(n), per))
            sample.offer(c * n + at, y[at])
        t = time.perf_counter() - t0
        idx, out = sample.taken()
        return Window(seconds=t, attempted=rows, failed=0, lost=0, rows=rows,
                      sample_idx=idx, sample_out=out)
