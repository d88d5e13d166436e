"""The one traffic generator, and the sink tail.

A traffic mix is a data file (``benchmark/traffic/<mix>.json``) that this
module reads; there is no per-mix code.

``mode: "backlog"``  saturating: the producer keeps the source topic's
                     unconsumed backlog just under ``high_water_rows``
                     (an upstream that is not in trouble: below the
                     overload manager's ELEVATED lag), ``chunk_rows`` at a
                     time.  An event is due the instant it is produced.
``mode: "paced"``    open loop: event ``i`` of the window is due at
                     ``t0 + i / rate_events_per_s`` whatever the system
                     does; the producer wakes every ``wake_ms`` and sends
                     what is due.  Lateness (sent minus due) is recorded.

Both run in the process that holds the chip (the broker is in-process and
the program has no ingest endpoint yet), on one thread each.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np


class Producer(threading.Thread):
    """Produces corpus events ``lo..hi`` into ``topic`` on the mix's
    schedule until stopped or out of events."""

    def __init__(self, traffic: Dict[str, Any], topic, make_record: Callable,
                 corpus, lo: int, hi: int, consumed: Callable[[], int]):
        super().__init__(name="bench-producer", daemon=True)
        self.traffic, self.topic, self.make_record = traffic, topic, make_record
        self.corpus, self.lo, self.hi, self.consumed = corpus, lo, hi, consumed
        self.produced = lo           # corpus index of the next event
        self.stop_event = threading.Event()
        self.t0: Optional[float] = None
        #: (first index, last index + 1, instant sent) per chunk
        self.chunks: List[Tuple[int, int, float]] = []
        self.exhausted = False
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            self.t0 = time.perf_counter()
            mode = self.traffic["mode"]
            if mode == "backlog":
                self._run_backlog()
            elif mode == "paced":
                self._run_paced()
            else:
                raise ValueError(f"unknown traffic mode {mode!r}")
        except BaseException as e:  # noqa: BLE001 — re-raised by the caller
            self.error = e

    def _send(self, hi: int) -> None:
        payloads, ts, produce, mk = (
            self.corpus.payloads, self.corpus.ts, self.topic.produce,
            self.make_record)
        for i in range(self.produced, hi):
            produce(mk(payloads[i], ts[i]))
        self.chunks.append((self.produced, hi, time.perf_counter()))
        self.produced = hi

    def _run_backlog(self) -> None:
        high, chunk = int(self.traffic["high_water_rows"]), int(self.traffic["chunk_rows"])
        while not self.stop_event.is_set():
            if self.produced >= self.hi:
                self.exhausted = True
                return
            if self.produced - self.consumed() < high:
                self._send(min(self.produced + chunk, self.hi))
            else:
                # the backlog is seconds of work deep: a long sleep starves
                # nobody, and every wake-up takes the GIL from the server
                time.sleep(0.010)

    def _run_paced(self) -> None:
        rate = float(self.traffic["rate_events_per_s"])
        wake = float(self.traffic["wake_ms"]) / 1e3
        while not self.stop_event.is_set():
            due = self.lo + int((time.perf_counter() - self.t0) * rate) + 1
            if due > self.hi:
                due, self.exhausted = self.hi, True
            if due > self.produced:
                self._send(due)
            if self.exhausted:
                return
            time.sleep(wake)

    # -------------------------------------------------- after the window
    def due_and_sent(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per event of the window (index - lo): the instant it was due
        and the instant it was sent, on ``perf_counter``'s clock."""
        n = self.produced - self.lo
        sent = np.empty(n)
        for a, b, t in self.chunks:
            sent[a - self.lo:b - self.lo] = t
        if self.traffic["mode"] == "paced":
            due = self.t0 + np.arange(n) / float(self.traffic["rate_events_per_s"])
        else:
            due = sent.copy()
        return due, sent


class SinkTail(threading.Thread):
    """Stamps sink records as they become readable: polls the topic's end
    offsets every ``every_ms`` and notes each growth.  Cheap enough to run
    beside the server (one lock-protected ``len`` per partition per poll);
    the records themselves are read once, after the window."""

    def __init__(self, topic, every_ms: float = 1.0):
        super().__init__(name="bench-sink-tail", daemon=True)
        self.topic, self.every = topic, every_ms / 1e3
        self.stop_event = threading.Event()
        self.start_ends: List[int] = list(topic.end_offsets())
        #: (instant, end offsets) at every growth seen
        self.marks: List[Tuple[float, List[int]]] = []

    def run(self) -> None:
        last = self.start_ends
        while not self.stop_event.is_set():
            ends = self.topic.end_offsets()
            if ends != last:
                self.marks.append((time.perf_counter(), ends))
                last = ends
            time.sleep(self.every)

    def readable_at(self, partition: int, n_records: int, start: int) -> np.ndarray:
        """For records ``start .. start + n_records`` of ``partition``, the
        instant each was first seen readable (NaN: never seen)."""
        out = np.full(n_records, np.nan)
        lo = start
        for t, ends in self.marks:
            hi = min(ends[partition], start + n_records)
            if hi > lo:
                out[lo - start:hi - start] = t
                lo = hi
        return out
