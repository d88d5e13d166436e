"""In-process partitioned log — the Kafka stand-in.

The reference's storage/transport layer is external Kafka (SURVEY §1 layer 0).
This framework's ingress/egress abstraction is a partitioned, offset-addressed
record log with the same semantics (keyed partitioning, per-partition
ordering, offsets, timestamps, tombstones).  The broker here is in-process;
a networked implementation can replace it behind the same interface.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from ksql_tpu.common import faults
from ksql_tpu.common.batch import stable_hash64
from ksql_tpu.common.errors import KsqlException


@dataclasses.dataclass
class Record:
    key: Any  # python value (tuple for multi-col keys) or None
    value: Any  # serialized payload (bytes/str) or None = tombstone
    timestamp: int
    partition: int = 0
    offset: int = -1
    # topic-global produce sequence — preserves total produce order across
    # partitions (the reference's TopologyTestDriver observes outputs in
    # produce order regardless of partition count)
    seq: int = -1
    headers: Tuple[Tuple[str, bytes], ...] = ()
    # windowed keys carry (window_start, window_end) alongside the key
    window: Optional[Tuple[int, int]] = None


class Topic:
    def __init__(self, name: str, partitions: int = 1):
        self.name = name
        self.num_partitions = partitions
        self.partitions: List[List[Record]] = [[] for _ in range(partitions)]
        self._seq = 0
        self._lock = threading.RLock()

    def partition_for(self, key: Any) -> int:
        if key is None:
            # round-robin-ish: stable on current size
            with self._lock:
                return sum(len(p) for p in self.partitions) % self.num_partitions
        return stable_hash64(key) % self.num_partitions

    def produce(self, record: Record) -> Record:
        if faults.armed():
            value = faults.fault_point("topic.produce", self.name, record.value)
            if value is not record.value:
                record = dataclasses.replace(record, value=value)
        with self._lock:
            p = record.partition if record.partition >= 0 else 0
            if record.partition < 0 or record.partition >= self.num_partitions:
                p = self.partition_for(record.key)
            part = self.partitions[p]
            # hot path: direct construction (dataclasses.replace dominates
            # the produce profile at high event rates)
            record = Record(
                record.key, record.value, record.timestamp, p, len(part),
                self._seq, record.headers, record.window,
            )
            self._seq += 1
            part.append(record)
            return record

    def produce_block(self, keys: List[Any], values: List[Any],
                      timestamps: List[int],
                      windows: List[Optional[Tuple[int, int]]]) -> List[Record]:
        """Append one record per ``(key, value, timestamp, window)`` under
        one hold of the lock: the partitions, offsets and ``seq`` that as
        many ``produce`` calls of partition -1 records give, each Record
        built once.  All or nothing: every record is built before any
        partition is extended.  ``topic.produce`` fault points fire per
        record, so a caller sends its records through ``produce`` while
        faults are armed."""
        with self._lock:
            seq = self._seq
            if self.num_partitions == 1:
                part = self.partitions[0]
                out = [
                    Record(k, v, t, 0, o, s, (), w)
                    for o, s, k, v, t, w in zip(
                        range(len(part), len(part) + len(keys)),
                        range(seq, seq + len(keys)),
                        keys, values, timestamps, windows,
                    )
                ]
                part.extend(out)
            else:
                ends = [len(p) for p in self.partitions]
                total = sum(ends)
                out = []
                for k, v, t, w in zip(keys, values, timestamps, windows):
                    # partition_for, with a null key's "current size" kept
                    # in hand (the lock is held: nothing else appends)
                    p = (
                        total if k is None else stable_hash64(k)
                    ) % self.num_partitions
                    out.append(Record(k, v, t, p, ends[p], seq, (), w))
                    ends[p] += 1
                    total += 1
                    seq += 1
                for r in out:
                    self.partitions[r.partition].append(r)
            self._seq += len(out)
            return out

    def read(self, partition: int, offset: int, max_records: int = 1024) -> List[Record]:
        with self._lock:
            out = self.partitions[partition][offset : offset + max_records]
        if faults.armed() and out:
            # one fault opportunity per record handed out, so a rule with
            # `after=` can deterministically tear the middle of a batch;
            # corruption replaces the handed-out copy, never the log
            faulted = []
            for r in out:
                value = faults.fault_point("topic.read", self.name, r.value)
                faulted.append(
                    r if value is r.value else dataclasses.replace(r, value=value)
                )
            return faulted
        return out

    def end_offsets(self) -> List[int]:
        with self._lock:
            return [len(p) for p in self.partitions]

    def all_records(self) -> List[Record]:
        """All records in global produce order (for tests/PRINT)."""
        with self._lock:
            out = [r for p in self.partitions for r in p]
        return sorted(out, key=lambda r: r.seq)


class Broker:
    """Topic registry (KafkaTopicClient analog)."""

    def __init__(self) -> None:
        self._topics: Dict[str, Topic] = {}
        self._lock = threading.RLock()

    def create_topic(self, name: str, partitions: int = 1, if_not_exists: bool = True) -> Topic:
        with self._lock:
            t = self._topics.get(name)
            if t is not None:
                if not if_not_exists:
                    raise KsqlException(f"Topic {name} already exists")
                return t
            t = Topic(name, partitions)
            self._topics[name] = t
            return t

    def topic(self, name: str) -> Topic:
        with self._lock:
            t = self._topics.get(name)
        if t is None:
            raise KsqlException(f"Topic {name} does not exist")
        return t

    def has_topic(self, name: str) -> bool:
        with self._lock:
            return name in self._topics

    def delete_topic(self, name: str) -> None:
        with self._lock:
            self._topics.pop(name, None)

    def list_topics(self) -> List[str]:
        with self._lock:
            return sorted(self._topics)


class Consumer:
    """Per-query consumer over a set of topics with committed offsets."""

    def __init__(self, broker: Broker, topics: List[str], from_beginning: bool = True):
        self.broker = broker
        self.topic_names = list(topics)
        self.positions: Dict[Tuple[str, int], int] = {}
        for tn in self.topic_names:
            t = broker.topic(tn)
            for p in range(t.num_partitions):
                self.positions[(tn, p)] = 0 if from_beginning else t.end_offsets()[p]

    def poll(self, max_records: int = 4096) -> List[Tuple[str, Record]]:
        """Merge-read across subscribed topic-partitions in global produce
        (seq) order per topic, so multi-partition intermediate topics are
        consumed in the order upstream emitted them (per-partition order is
        a fortiori preserved).

        Heap-merge over per-partition cursors (each partition is already
        seq-ordered): O(taken · log P), instead of speculatively reading the
        full budget from every partition and discarding the overflow."""
        import heapq

        out: List[Tuple[str, Record]] = []
        budget = max_records
        for tn in self.topic_names:
            if budget <= 0:
                break
            t = self.broker.topic(tn)

            def part_iter(p: int, start: int):
                offset = start
                while True:
                    chunk = t.read(p, offset, 256)
                    if not chunk:
                        return
                    for r in chunk:
                        yield r.seq, p, r
                    offset += len(chunk)

            merged = heapq.merge(
                *(part_iter(p, self.positions[(tn, p)]) for p in range(t.num_partitions))
            )
            taken = 0
            for _seq, p, r in merged:
                if taken >= budget:
                    break
                self.positions[(tn, p)] += 1
                out.append((tn, r))
                taken += 1
            budget -= taken
        return out

    def fork(self, positions: Optional[Dict[Tuple[str, int], int]] = None
             ) -> "Consumer":
        """A new consumer over the same topics at ``positions`` (default:
        a copy of the current positions).  The tick-deadline watchdog uses
        this to fence an abandoned tick worker: the zombie keeps mutating
        the orphaned consumer while the query resumes on the fork."""
        c = Consumer.__new__(Consumer)
        c.broker = self.broker
        c.topic_names = list(self.topic_names)
        c.positions = dict(self.positions if positions is None else positions)
        return c

    def at_end(self) -> bool:
        for tn in self.topic_names:
            t = self.broker.topic(tn)
            ends = t.end_offsets()
            for p in range(t.num_partitions):
                if self.positions[(tn, p)] < ends[p]:
                    return False
        return True
