"""The one traffic generator: it reads a mix's data file and a cell's
parameters and produces, from the seed, the stream of submissions and
the pusher thread that sends them.

Every seed gets the same pool rows, weights and gaps in each pass over
them, in another order, so that two seeds offer the same work and
differ only in its arrangement.

Arrival kinds (``arrivals`` in the mix's file):

``backlog``  closed loop: a refill thread keeps the job's gateway queue
             at ``queue_quota`` pending updates and never above it, so
             nothing is shed and a cohort is always waiting.
``poisson``  open loop at ``rate_per_s``: exponential gaps (the
             quantiles of ``block`` arrivals, permuted per block, so
             the rate holds over every block); each update is timed
             from when it was due, whatever the service did meanwhile.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

ARRIVALS = ("backlog", "poisson")
WAIT_SLICE_S = 0.002


@dataclass(frozen=True)
class Submission:
    seq: int
    pool: int          # row of the update pool
    weight: float      # the client's sample count
    gap_s: float       # wait after the previous submission was due

    @property
    def client_id(self) -> str:
        return f"u{self.seq}"


def _cycle(rng: np.random.Generator, values: np.ndarray) -> Iterator:
    """``values`` over and over, each pass in a new seeded order."""
    while True:
        yield from rng.permutation(values).tolist()


def submissions(traffic: Dict[str, Any], params: Dict[str, Any],
                seed: int) -> Iterator[Submission]:
    """Endless seeded stream of submissions for one cell.  Pool rows,
    weights and gaps are three independent cycles: every pass over the
    pool's rows, over the weight range and over ``block`` arrivals holds
    each value once, in an order drawn from the seed."""
    kind = traffic["arrivals"]
    if kind not in ARRIVALS:
        raise ValueError(f"unknown arrivals {kind!r}; known {ARRIVALS}")
    lo, hi = (int(v) for v in params["weights"])
    if kind == "poisson":
        # the block's exponential quantiles, scaled to a mean of
        # exactly 1/rate: every ``block`` arrivals span the same time
        block = int(traffic["block"])
        q = -np.log1p(-(np.arange(block) + 0.5) / block)
        gaps = q / q.mean() / float(params["rate_per_s"])
    else:
        gaps = np.zeros(1)
    rngs = [np.random.default_rng([int(seed), k]) for k in range(3)]
    rows = _cycle(rngs[0], np.arange(int(params["pool_updates"])))
    weights = _cycle(rngs[1], np.arange(lo, hi, dtype=np.float64))
    waits = _cycle(rngs[2], gaps)
    seq = 0
    while True:
        yield Submission(seq, int(next(rows)), float(next(weights)),
                         float(next(waits)))
        seq += 1


@dataclass
class Sent:
    sub: Submission
    due: float          # perf_counter when it was due
    t_admit: float      # perf_counter when the gateway admitted it
    admit_s: float      # duration of the admitting submit call
    shed: int           # busy replies before it was admitted
    late_s: float       # how late the generator made its first attempt


class Pusher:
    """Sends a cell's submissions from one thread, from ``start`` until
    stopped.

    ``submit(sub) -> verdict`` is the gateway call; a busy verdict is
    retried after its ``retry_after_s`` (the update keeps its due time,
    so the wait counts against its latency)."""

    def __init__(self, traffic: Dict[str, Any], params: Dict[str, Any],
                 seed: int, submit: Callable[[Submission], Dict[str, Any]],
                 depth: Callable[[], int]):
        self.kind = traffic["arrivals"]
        self.refill_s = float(traffic.get("refill_period_s", 0.001))
        self.quota = int(params["queue_quota"])
        self._stream = submissions(traffic, params, seed)
        self._submit = submit
        self._depth = depth
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.sent: Dict[str, Sent] = {}
        self.error: Optional[BaseException] = None

    def prime(self, n: int) -> None:
        """Submit the next ``n`` submissions now, from the calling
        thread: the warm rounds' cohorts, queued before the clock of an
        open loop starts."""
        t = time.perf_counter()
        for _ in range(n):
            self._send(next(self._stream), t)

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"pusher-{self.kind}")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            if self._thread.is_alive():
                raise RuntimeError("pusher thread did not stop")

    def _send(self, sub: Submission, due: float) -> None:
        shed = 0
        late = time.perf_counter() - due
        while not self._stop.is_set():
            t = time.perf_counter()
            verdict = self._submit(sub)
            t1 = time.perf_counter()
            if not verdict["busy"]:
                if not verdict["admitted"]:
                    raise RuntimeError(f"{sub.client_id} refused: {verdict}")
                self.sent[sub.client_id] = Sent(sub, due, t1, t1 - t, shed,
                                                late)
                return
            shed += 1
            self._stop.wait(verdict["retry_after_s"])

    def _run(self) -> None:
        try:
            if self.kind == "backlog":
                self._run_backlog()
            else:
                self._run_poisson()
        except BaseException as e:        # surfaced by the harness
            self.error = e

    def _run_backlog(self) -> None:
        while not self._stop.is_set():
            for _ in range(self.quota - self._depth()):
                t = time.perf_counter()
                self._send(next(self._stream), t)
            self._stop.wait(self.refill_s)

    def _run_poisson(self) -> None:
        due = time.perf_counter()
        for sub in self._stream:
            due += sub.gap_s
            # short waits: one long timed wait can overshoot by seconds
            # on a loaded host, and the overshoot would count against
            # the service as latency
            while (delay := due - time.perf_counter()) > 0:
                if self._stop.wait(min(delay, WAIT_SLICE_S)):
                    return
            if self._stop.is_set():
                return
            self._send(sub, due)
