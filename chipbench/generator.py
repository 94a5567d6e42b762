"""The one traffic generator. A traffic file's parameters:

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last one returned) or ``"open"`` (requests due on a
  schedule, whether or not earlier ones returned);
- ``rate_per_s`` (open): mean arrival rate. The gaps between arrivals are
  the ``rate * seconds`` quantiles of the exponential distribution, in
  an order drawn from ``schedule_seed`` (default 0), not from the run's
  seed: a tail latency depends on where the short gaps bunch, so every
  run of a cell is offered the same arrivals and the run's seed draws
  what the requests carry;
- ``warmup``: requests sent before the window, not measured;
- ``sample``: how many responses the correctness check compares, drawn
  from the seed among all that completed in the window;
- anything else (such as ``values``) is read by the deployment.

A request's latency runs from when it was due (open) or sent (closed)
to when its response reached the client.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Optional

import numpy as np

WAIT_PAST_CLOSE_S = 60.0


def rng(seed: int, *stream: int) -> np.random.Generator:
    """The generator of one stream of a run's random numbers."""
    return np.random.default_rng([int(seed) % 2**64, *stream])


@dataclasses.dataclass
class Record:
    k: int
    due: float                     # perf_counter seconds
    sent: float = 0.0
    done: Optional[float] = None
    error: str = ""                # "" served; "shed: ..." or "error: ..."
    degraded: bool = False
    downgraded: bool = False
    kernel_path: str = ""

    @property
    def latency(self) -> float:
        return float("inf") if self.done is None else self.done - self.due


class Reservoir:
    """A uniform sample of at most ``size`` items from a stream of
    unknown length, drawn from ``rng`` (Algorithm R)."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen = int(size), rng, 0
        self.items: list = []
        self._mu = threading.Lock()

    def offer(self, item) -> None:
        with self._mu:
            self.seen += 1
            if len(self.items) < self.size:
                self.items.append(item)
                return
            j = int(self.rng.integers(0, self.seen))
            if j < self.size:
                self.items[j] = item


def _send(server, payload, reuse_hint: int):
    a, b = payload
    return server.submit(a, b, reuse_hint=reuse_hint)


def _finish(rec: Record, ticket, deadline: float, payload, keep) -> None:
    from repro.resilience.errors import OverloadError
    try:
        resp = ticket.result(max(deadline - time.perf_counter(), 0.0))
    except TimeoutError:
        rec.error = "error: no response within a minute of the close"
        return
    except OverloadError as e:
        rec.done = None
        rec.error = f"shed: {e}"
        return
    except Exception as e:             # noqa: BLE001 - recorded, checked
        rec.error = f"error: {type(e).__name__}: {e}"
        return
    rec.done = time.perf_counter()
    rec.degraded = bool(resp.degraded)
    rec.downgraded = bool(resp.downgraded)
    rec.kernel_path = resp.kernel_path
    keep.offer((payload, resp.result))


def warm_up(server, dep, traffic: dict, reuse_hint: int) -> None:
    for i in range(int(traffic.get("warmup", 2))):
        _send(server, dep.payload(i, warm=True), reuse_hint).result()


def run(server, dep, traffic: dict, seconds: float, reuse_hint: int,
        keep: Reservoir) -> list[Record]:
    """Drive ``server`` with the traffic for ``seconds``; every request
    sent in the window is waited for (at most a minute past the close)."""
    loop = traffic["loop"]
    if loop == "closed":
        return _closed(server, dep, int(traffic.get("clients", 1)),
                       seconds, reuse_hint, keep)
    if loop == "open":
        return _open(server, dep, float(traffic["rate_per_s"]), seconds,
                     int(traffic.get("schedule_seed", 0)), reuse_hint, keep)
    raise ValueError(f"unknown loop {loop!r}")


def _closed(server, dep, clients: int, seconds: float, reuse_hint: int,
            keep: Reservoir) -> list[Record]:
    from repro.resilience.errors import OverloadError
    records: list[Record] = []
    mu = threading.Lock()
    counter = iter(range(1 << 62))
    t_end = time.perf_counter() + seconds

    def client():
        while True:
            with mu:
                k = next(counter)
            payload = dep.payload(k)
            now = time.perf_counter()
            if now >= t_end:
                return
            rec = Record(k=k, due=now, sent=now)
            with mu:
                records.append(rec)
            try:
                ticket = _send(server, payload, reuse_hint)
            except OverloadError as e:
                rec.error = f"shed: {e}"
                continue
            _finish(rec, ticket, t_end + WAIT_PAST_CLOSE_S, payload, keep)

    threads = [threading.Thread(target=client, name=f"chipbench-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(records, key=lambda r: r.k)


def arrival_gaps(rate: float, seconds: float, schedule_seed: int
                 ) -> np.ndarray:
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return rng(schedule_seed, 2).permutation(gaps)


def _open(server, dep, rate: float, seconds: float, schedule_seed: int,
          reuse_hint: int, keep: Reservoir) -> list[Record]:
    from repro.resilience.errors import OverloadError
    gaps = arrival_gaps(rate, seconds, schedule_seed)
    ready: queue.Queue = queue.Queue(maxsize=16)
    sent: queue.Queue = queue.Queue()

    def produce():                     # payloads made ahead of their due
        for k in range(len(gaps)):
            ready.put((k, dep.payload(k)))

    producer = threading.Thread(target=produce, name="chipbench-payloads")
    producer.start()
    first = ready.get()
    t0 = time.perf_counter()
    dues = t0 + np.cumsum(gaps)
    t_close = t0 + seconds
    records: list[Record] = []

    def collect():
        while True:
            item = sent.get()
            if item is None:
                return
            rec, ticket, payload = item
            _finish(rec, ticket, max(t_close, rec.due) + WAIT_PAST_CLOSE_S,
                    payload, keep)

    collector = threading.Thread(target=collect, name="chipbench-collect")
    collector.start()
    item = first
    for k in range(len(gaps)):
        if k:
            item = ready.get()
        _, payload = item
        rec = Record(k=k, due=float(dues[k]))
        records.append(rec)
        delay = rec.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        rec.sent = time.perf_counter()
        try:
            ticket = _send(server, payload, reuse_hint)
        except OverloadError as e:
            rec.error = f"shed: {e}"
            continue
        sent.put((rec, ticket, payload))
    sent.put(None)
    producer.join()
    collector.join()
    return records
