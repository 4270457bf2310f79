"""The one general load generator.  A traffic file
(``bench/traffic/<mix>.json``) picks the loop and its parameters:

closed loop (``"loop": "closed"``): one client sends ``batch`` samples per
    call, back to back, each call ended by copying its result to the host.
    ``distinct_batches`` batches drawn from the seed are sent in turn;
    the samples per second over the window are reported under the
    end-to-end metric ``rate_metric`` names.
open loop (``"loop": "open"``): Poisson arrivals at ``rate_hz`` of single
    samples drawn from a pool of ``distinct_records``; each request is
    timed from the moment it was due to the moment the client holds its
    result, so a stalled generator charges its lateness to the requests.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np


def poisson_arrivals(n: int, rate_hz: float, rng) -> np.ndarray:
    """Open-loop Poisson process: cumulative arrival offsets in seconds
    (a copy of repro's ``benchmarks/serving_load.poisson_arrivals``)."""
    return np.cumsum(rng.exponential(1.0 / rate_hz, n))


def fixed_count_schedule(rate_hz: float, seconds: float, rng) -> np.ndarray:
    """``round(rate_hz * seconds)`` Poisson arrivals conditioned to fall in
    ``[0, seconds)``: every seed offers the same number of requests, at
    the same mean rate, in a different order of gaps."""
    n = max(1, round(rate_hz * seconds))
    cum = poisson_arrivals(n + 1, rate_hz, rng)
    return cum[:n] * (seconds / cum[n])


def no_span(name: str):
    return contextlib.nullcontext()


@dataclasses.dataclass
class ClosedWindow:
    t0: float
    t1: float  # when the last call's result reached the host
    calls: list  # (batch index, host outputs) per call


def closed_loop(call, batches: list, seconds: float, *, clock=time.perf_counter,
                span=no_span, first: int = 0) -> ClosedWindow:
    """Back-to-back calls until ``seconds`` have passed; the call that
    crosses the limit finishes and counts."""
    calls = []
    t0 = clock()
    k = first
    while True:
        with span("enqueue"):
            y = call(batches[k % len(batches)])
        with span("block"):
            y = np.asarray(y)
        calls.append((k % len(batches), y))
        k += 1
        t = clock()
        if t - t0 >= seconds:
            return ClosedWindow(t0, t, calls)


@dataclasses.dataclass
class OpenWindow:
    due: np.ndarray  # absolute due times
    sent: np.ndarray  # when submit returned (nan: never sent)
    done: np.ndarray  # when the client held the result (inf: never)
    refused: np.ndarray  # the server refused it at the door (queue full)
    rows: np.ndarray  # pool row each request carried
    outs: np.ndarray  # output row per request (valid where done is finite)
    t_end: float  # end of the offered window

    @property
    def latency_s(self) -> np.ndarray:
        return self.done - self.due

    @property
    def lag_s(self) -> np.ndarray:
        return self.sent - self.due


def open_loop(batcher, records: np.ndarray, rows: np.ndarray,
              offsets: np.ndarray, t_start: float, seconds: float, *,
              drain_s: float, clock=time.perf_counter,
              span=no_span) -> OpenWindow:
    """Submit request ``i`` (``records[rows[i]]``) when it is due, poll the
    batcher between submissions, and after the last one keep polling
    until every request is answered or ``drain_s`` has passed.

    Everything the client keeps lives in preallocated arrays: a growing
    heap of per-request objects would make the collector's full passes,
    and so the stalls of the one thread that also serves, grow with the
    window."""
    from repro.serving.queue import QueueFull

    n = len(offsets)
    due = t_start + np.asarray(offsets, np.float64)
    sent = np.full(n, np.nan)
    done = np.full(n, np.inf)
    refused = np.zeros(n, bool)
    outs = None
    index_of: dict = {}
    t_end = t_start + seconds
    i = 0
    while True:
        now = clock()
        if i < n and due[i] <= now:
            with span("submit"):
                while i < n and due[i] <= now:
                    try:
                        index_of[batcher.submit(records[rows[i]])] = i
                    except QueueFull:
                        refused[i] = True  # no answer: +inf latency
                    sent[i] = clock()
                    i += 1
        with span("poll"):
            finished = batcher.poll()
        if finished:
            t = clock()
            for rid in finished:
                j = index_of.pop(rid, None)
                res = batcher.pop_result(rid)
                if j is not None and res is not None and not res.shed:
                    if outs is None:
                        outs = np.zeros((n, np.size(res.out)), np.float64)
                    done[j] = t
                    outs[j] = np.ravel(res.out)
        if i >= n and (not index_of or now >= t_end + drain_s):
            if outs is None:
                outs = np.zeros((n, 1))
            return OpenWindow(due, sent, done, refused, np.asarray(rows), outs,
                              t_end)
