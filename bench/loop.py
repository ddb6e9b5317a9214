"""The client side of a run: a batcher in front of the system under test,
driven by a closed or an open loop, on the host clock.

The batcher is a copy of the collect-and-pad step of the program's analytics
server (``repro.launch.serve``): it takes the shape at the head of the
queue, collects up to ``bucket`` queued requests of that shape, pads the
batch to ``bucket`` rows by repeating the last binding, and makes one call.
Each step runs inside a ``jax.profiler.TraceAnnotation`` span
(``bench.collect``, ``bench.run_batch``, ``bench.respond``, ``bench.wait``)
so that a trace can attribute the device's idle time to them."""
from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np
from jax.profiler import TraceAnnotation

from .traffic import ClosedClients, Draw

#: Seconds past the window's close that a due request may still take.
DRAIN_S = 60.0


@dataclass
class Request:
    id: int
    shape: str
    params: dict[str, int]
    due: float
    client: int = -1
    done: float = math.nan
    batch: int = -1
    status: str = ""
    value: Any = None


@dataclass
class Batch:
    shape: str
    start: float
    end: float
    rows: int  # requests in it, padding left out
    answered: int  # of them, those that did not end in an error


@dataclass
class Record:
    """What one measured window produced, on the host clock."""

    t0: float = 0.0  # window start
    t_close: float = 0.0  # window start + seconds
    t_end: float = 0.0  # the close, or the end of the last batch begun before it
    requests: list[Request] = field(default_factory=list)
    batches: list[Batch] = field(default_factory=list)

    def close(self) -> None:
        """End the window with the last batch that started inside it."""
        ends = [b.end for b in self.batches if b.start < self.t_close]
        self.t_end = max([self.t_close] + ends)

    def in_window(self) -> list[Request]:
        """Requests whose batch started inside the window."""
        return [r for r in self.requests
                if r.batch >= 0 and self.batches[r.batch].start < self.t_close]


class Batcher:
    """``execute(shape, arrays) -> outcomes`` behind serve's collect-and-pad."""

    def __init__(self, execute: Callable, bucket: int, keep: Callable[[Request], bool]):
        self.execute = execute
        self.bucket = bucket
        self.keep = keep

    def step(self, queue: deque, rec: Record) -> list[Request]:
        with TraceAnnotation("bench.collect"):
            head = queue.popleft()
            group, skipped = [head], deque()
            while queue and len(group) < self.bucket:
                item = queue.popleft()
                (group if item.shape == head.shape else skipped).append(item)
            queue.extendleft(reversed(skipped))
            pad = self.bucket - len(group)
            arrays = {k: np.asarray([r.params[k] for r in group]
                                    + [group[-1].params[k]] * pad)
                      for k in head.params}
        start = time.perf_counter()
        with TraceAnnotation("bench.run_batch"):
            outcomes = self.execute(head.shape, arrays)
        end = time.perf_counter()
        with TraceAnnotation("bench.respond"):
            rec.batches.append(Batch(head.shape, start, end, len(group),
                                     sum(oc.status != "error" for oc in outcomes[:len(group)])))
            for r, oc in zip(group, outcomes):
                r.done, r.batch, r.status = end, len(rec.batches) - 1, oc.status
                if oc.status != "error" and self.keep(r):
                    r.value = np.array(oc.value)  # a copy frees the batch's array
        return group


def run_closed(batcher: Batcher, clients: ClosedClients, seconds: float,
               warmup_batches: int) -> Record:
    """``clients.n`` callers with no think time. The first ``warmup_batches``
    batches are served before the window opens."""
    rec = Record()
    ids = iter(range(1 << 62))
    now = time.perf_counter()

    def issue(c: int, due: float) -> Request:
        d: Draw = clients.next(c)
        r = Request(next(ids), d.shape, d.params, due, client=c)
        rec.requests.append(r)
        return r

    queue = deque(issue(c, now) for c in range(clients.n))
    warm = Record()
    for _ in range(warmup_batches):
        for r in batcher.step(queue, warm):
            queue.append(issue(r.client, r.done))
    rec.requests = [r for r in queue]
    for r in rec.requests:
        r.due = math.nan  # issued before the window: not timed from a due time
    rec.t0 = time.perf_counter()
    rec.t_close = rec.t0 + seconds
    with TraceAnnotation("bench.window"):
        while time.perf_counter() < rec.t_close:
            for r in batcher.step(queue, rec):
                queue.append(issue(r.client, r.done))
    rec.close()
    return rec


def run_open(batcher: Batcher, arrivals: list[tuple[float, Draw]],
             warmup: list[Draw], seconds: float) -> Record:
    """Requests arrive at fixed offsets from the window's start, whatever the
    system does; each is timed from when it was due. After the close the
    queue drains, up to ``DRAIN_S``."""
    warm, queue = Record(), deque()
    now = time.perf_counter()
    queue.extend(Request(-1, d.shape, d.params, now) for d in warmup)
    while queue:
        batcher.step(queue, warm)
    rec = Record()
    rec.t0 = time.perf_counter()
    rec.t_close = rec.t0 + seconds
    rec.requests = [Request(i, d.shape, d.params, rec.t0 + t)
                    for i, (t, d) in enumerate(arrivals)]
    pending = deque(rec.requests)
    with TraceAnnotation("bench.window"):
        while pending or queue:
            now = time.perf_counter()
            while pending and pending[0].due <= now:
                queue.append(pending.popleft())
            if queue:
                if now > rec.t_close + DRAIN_S:
                    break
                batcher.step(queue, rec)
                continue
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(pending[0].due - now, 0.05)))
    rec.close()
    return rec
