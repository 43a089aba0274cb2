"""The load generator: one client thread driving the serving front end.

A traffic mix (``bench/traffic/<mix>.json``) is data read by this one
generator.  ``loop: "closed"`` keeps ``in_flight`` requests outstanding: a
new one is sent as soon as the oldest returns, so a slow server receives
less load, as callers that wait for their answer do.  Each request carries
``rows_per_request`` images taken in turn from a pool of ``pool_images``
images, visited in an order drawn from the seed.

The first ``warmup_requests`` requests warm the server up and belong to
set-up.  The window opens at the next submit and lasts ``seconds``; after
it closes no request is sent and the outstanding ones are awaited.  The
client's own spans (``client.submit``, ``client.wait``) name the idle gaps
of a profiler trace when one is being taken: the benchmark records them itself
(``Record``), and ``bench/trace.py`` puts them on the device clock.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass

import numpy as np

RESULT_TIMEOUT_S = 120.0  # an answer later than this counts as never sent


@dataclass
class Record:
    """One request as the client saw it, on the client's clock."""

    images: tuple[int, ...]  # pool indices of its rows
    submit_s: float  # before the submit call
    admitted_s: float  # after the submit call returned
    wait_s: float | None = None  # when the client began to wait for it
    done_s: float | None = None  # when the result reached the client
    logits: np.ndarray | None = None  # the answer, None if it failed
    error: str | None = None


@dataclass
class Run:
    records: list[Record]
    window_start_s: float
    window_end_s: float

    def in_window(self, t: float) -> bool:
        return self.window_start_s <= t < self.window_end_s


def visit_order(traffic: dict, seed: int) -> np.ndarray:
    """The order in which the pool's images are sent: a permutation of the
    whole pool, drawn from the seed, so every seed sends the same images."""
    rng = np.random.default_rng([int(seed), 0xC11E])
    return rng.permutation(traffic["pool_images"])


def closed_loop(frontend, pool: np.ndarray, traffic: dict, seed: int,
                seconds: float, on_window_start=None) -> Run:
    """Drive ``frontend`` with the closed-loop mix ``traffic`` for a window
    of ``seconds`` after the warm-up; ``on_window_start(t)`` is called just
    before the first timed submit, and the window opens when it returns."""
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown traffic loop {traffic['loop']!r}")
    order = visit_order(traffic, seed)
    rows = traffic["rows_per_request"]
    warmup = traffic["warmup_requests"]
    records: list[Record] = []
    outstanding: deque = deque()
    window = [None, None]

    def send() -> None:
        k = len(records) * rows
        idx = tuple(int(order[(k + r) % len(order)]) for r in range(rows))
        x = pool[list(idx)]
        t0 = time.perf_counter()
        handle = frontend.submit(x)
        rec = Record(idx, t0, time.perf_counter())
        records.append(rec)
        outstanding.append((handle, rec))

    def open_window() -> bool:
        """True while the loop should keep sending."""
        if len(records) < warmup:
            return True
        now = time.perf_counter()
        if window[0] is None:
            if on_window_start is not None:
                on_window_start(now)
            now = time.perf_counter()
            window[0], window[1] = now, now + seconds
            return True
        return now < window[1]

    for _ in range(traffic["in_flight"]):
        if not open_window():
            break
        send()
    while outstanding:
        handle, rec = outstanding.popleft()
        rec.wait_s = time.perf_counter()
        try:
            result = handle.result(timeout=RESULT_TIMEOUT_S)
        except TimeoutError as err:
            rec.error = f"no answer: {err}"
            break
        rec.done_s = time.perf_counter()
        if result.ok:
            rec.logits = np.asarray(result.logits)
        else:
            rec.error = f"{type(result.error).__name__}: {result.error}"
        if open_window():
            send()
    for handle, rec in outstanding:  # left behind by a lost answer
        rec.error = rec.error or "not awaited after an earlier lost answer"
    return Run(records, window[0], window[1])
