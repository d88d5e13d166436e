#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: the engine, ``KsqlServer`` and its own poll
loop, the generator thread and the sink tail.  Set-up builds the corpus
from ``--seed``, issues the configuration's statements over HTTP, fills
the state through the engine's own ``poll_once`` at batch capacity, and
drives one tick at the served tick size; the window then offers the
traffic mix to the served loop for ``--seconds``; afterwards what the
timed run left on the sink topic, in the live store and behind pull
queries is compared with the deployment's plain reference.

The last line of standard output is the result (one JSON object).  With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read by the data files under
``benchmark/layer_metrics/``.  Exit code 2 and no result without a TPU
(or with fewer chips than the cell asks for); ``--rehearse`` runs the
configuration's tiny sizes on whatever platform JAX has and says so.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import urllib.request  # noqa: E402
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: processing-log categories that report the system working as designed
_PLOG_INFORMATIONAL = ("overload.", "telemetry.", "deadline.hint")


def say(step: str, **facts: Any) -> None:
    print(f"BENCH {step} " + json.dumps(facts, sort_keys=True, default=str),
          file=sys.stderr, flush=True)


class HarnessError(RuntimeError):
    """The run cannot be measured (not: the program answered wrongly)."""


# ------------------------------------------------------------ data files
def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def merged(base: Dict[str, Any], over: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """``base`` with ``over`` laid on top, one level into dicts."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_cell(workload: str, rehearse: bool):
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise HarnessError(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearse:
        config = merged(config, config.get("rehearse"))
        traffic = merged(traffic, traffic.get("rehearse"))
    # a mix may re-shape the corpus (key universe, skew); the state's
    # scale stays the configuration's
    config["sizes"] = {**config["sizes"], **traffic.get("sizes", {})}
    return bench, cell, config, traffic


def load_deployment(name: str):
    path = os.path.join(HERE, "deployments", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_deployment_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


# ------------------------------------------------------------------ HTTP
def post(url: str, path: str, body: Dict[str, Any], timeout: float = 120.0) -> Any:
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


# ---------------------------------------------------------------- device
def device_facts(jax) -> Dict[str, Any]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps no
    such statistic, as the CPU of a rehearsal)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


# ------------------------------------------------------ the program's spans
def stage_totals(recorder) -> Dict[str, Dict[str, float]]:
    """Cumulative per-stage totals of the flight recorder (they never
    trim; its percentiles cover a 64-tick ring and are not read)."""
    out: Dict[str, Dict[str, float]] = {}
    for name, st in recorder.stage_stats().items():
        out[name] = {k: float(v) for k, v in st.items()
                     if isinstance(v, (int, float)) and k not in ("ticks", "p50_ms", "p99_ms")}
    return out


def stage_deltas(before, after) -> Dict[str, float]:
    obs: Dict[str, float] = {}
    for name, st in after.items():
        for k, v in st.items():
            obs[f"span.{name}.{k}"] = v - before.get(name, {}).get(k, 0.0)
    return obs


class GcLog:
    """The collector's pauses inside the window.  CPython's collector stops
    every thread of the process, the server's poll loop with them.  The
    heap is frozen at window start: nearly
    all of what the collector tracks by then is the in-process broker's log
    of the fill (millions of ``Record`` objects that a deployment's broker
    holds in another process), but the freeze takes the program's other
    long-lived objects out of the collector's reach too.  Each
    configuration states this under ``assumed``."""

    def __init__(self) -> None:
        self.pauses: List[Tuple[int, float]] = []  # (generation, seconds)
        self._t = 0.0

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def start(self) -> None:
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self)

    def stop(self) -> Dict[str, float]:
        """Stop listening.  Nothing is unfrozen: a full collection over the
        corpus would stop every thread for a second or more while the last
        answers are still being waited for."""
        gc.callbacks.remove(self)
        return {
            "gc.pause_ms_total": 1e3 * sum(d for _, d in self.pauses),
            "gc.pause_ms_max": 1e3 * max((d for _, d in self.pauses), default=0.0),
            "gc.collections": float(len(self.pauses)),
        }


class TickLog:
    """Keeps each recorded tick's spans on ``perf_counter``'s clock (traced
    runs only: idle gaps of the device are named after them)."""

    def __init__(self, recorder):
        self.spans: List[Tuple[str, float, float, int]] = []
        self._prev = recorder.observer
        self._recorder = recorder
        recorder.observer = self._on_tick

    def _on_tick(self, trace) -> None:
        t0 = trace._t0
        for s in trace.spans:
            a = t0 + s["t0Ms"] / 1e3
            self.spans.append((s["name"], a, a + s["durMs"] / 1e3, s["depth"]))
        if self._prev is not None:
            self._prev(trace)

    def close(self) -> None:
        self._recorder.observer = self._prev


# ------------------------------------------------------------- the run
class Run:
    """One cell, one seed: set-up, window, comparison."""

    def __init__(self, args, bench, cell, config, traffic, deployment, jax):
        self.args, self.bench, self.cell = args, bench, cell
        self.config, self.traffic, self.dep, self.jax = config, traffic, deployment, jax
        self.obs: Dict[str, float] = {}
        self.numbers: Dict[str, Dict[str, float]] = {}
        self.breakdown: Optional[Dict[str, Any]] = None
        #: test seam: called with the run just before the window opens
        self.before_window: Optional[Callable[["Run"], None]] = None

    # ---- set-up ------------------------------------------------------
    def setup(self, window_events_total: Optional[int] = None) -> None:
        from ksql_tpu import native
        from ksql_tpu.common.config import KsqlConfig
        from ksql_tpu.engine.engine import KsqlEngine
        from ksql_tpu.runtime.topics import Record
        from ksql_tpu.server.rest import KsqlServer

        cfg, sizes = self.config, self.config["sizes"]
        seconds = float(self.args.seconds)
        self.fill = int(sizes["fill_events"])
        self.warm = int(sizes["warm_ticks"]) * int(sizes["warm_tick_events"])
        self.n_events = self.fill + self.warm  # events offered so far
        n_window = window_events_total or window_events(self.traffic, seconds)
        t = time.perf_counter()
        self.corpus = self.dep.make_corpus(
            self.args.seed, sizes, self.fill + self.warm + n_window)
        say("corpus", events=len(self.corpus.payloads), fill=self.fill,
            warm=self.warm, window_max=n_window,
            payload_bytes_mean=round(statistics.fmean(map(len, self.corpus.payloads[:2000])), 1),
            seconds=round(time.perf_counter() - t, 2))
        if not native.available():
            raise HarnessError(f"native ingest library: {native.build_error()}")

        self.Record = Record
        self.engine = KsqlEngine(KsqlConfig(dict(cfg["engine_props"])))
        self.srv = KsqlServer(engine=self.engine, port=0)
        self.srv.start()
        out = post(self.srv.url, "/ksql", {"ksql": " ".join(cfg["statements"])})
        self.qid = [e["commandStatus"]["queryId"] for e in out
                    if e.get("commandStatus", {}).get("queryId")][-1]
        self.handle = self.engine.queries[self.qid]
        self.ex = self.handle.executor
        want_backend = cfg["engine_props"].get("ksql.runtime.backend", "device")
        if self.handle.backend != want_backend:
            raise HarnessError(
                f"query runs on {self.handle.backend!r}, configuration asks "
                f"{want_backend!r}: {dict(self.engine.fallback_reasons)}")
        if bool(cfg["native_ingest"]) != (self.ex._native_fields is not None):
            raise HarnessError(
                f"configuration states native_ingest={cfg['native_ingest']}, the "
                f"plan's executor has it {'on' if self.ex._native_fields is not None else 'off'}")
        self.source = self.engine.broker.topic(self.corpus.source_topic)
        self.sink = self.engine.broker.topic(self.handle.plan.physical_plan.topic)
        self.recorder = self.engine.trace_recorder(self.qid)
        self.obs["config.ksql.batch.capacity"] = float(self.ex.device.capacity)

        # table loads, then the state fill: through the engine's own tick
        # at batch capacity, under the server's engine lock, one chunk
        # produced and drained at a time (a backlog over the overload
        # manager's ELEVATED lag would clamp every tick)
        t = time.perf_counter()
        cap = int(self.ex.device.capacity)
        with self.srv.engine_lock:
            for topic_name, rows in self.corpus.preload:
                topic = self.engine.broker.topic(topic_name)
                for lo in range(0, len(rows), cap):
                    for key, value, ts in rows[lo:lo + cap]:
                        topic.produce(Record(key=key, value=value, timestamp=ts))
                    self._drain_locked(cap)
            say("preload", tables=[(n, len(r)) for n, r in self.corpus.preload],
                seconds=round(time.perf_counter() - t, 2))
            t = time.perf_counter()
            for lo in range(0, self.fill, cap):
                self._produce(lo, min(lo + cap, self.fill))
                self._drain_locked(cap)
        say("fill", events=self.fill, seconds=round(time.perf_counter() - t, 2),
            sink_records=sum(self.sink.end_offsets()))

        # warm ticks at the served tick size, through the server's own
        # loop: enough of them that every program the window will run has
        # run (the store's retention pass comes every 64th batch)
        t = time.perf_counter()
        step = int(sizes["warm_tick_events"])
        for lo in range(self.fill, self.fill + self.warm, step):
            self._produce(lo, lo + step)
            self.wait_quiet(lo + step, 120.0)
        say("warm_ticks", events=self.warm, seconds=round(time.perf_counter() - t, 2))
        self.check_running("after set-up")
        actions = self.engine.overload.stats()["actions-total"]
        say("overload", actions_total=actions, level=self.engine.overload.stats()["level"])
        if any(actions.values()):
            raise HarnessError(f"overload ladder engaged during set-up: {actions}")

    def _produce(self, lo: int, hi: int) -> None:
        payloads, ts, Record, produce = (
            self.corpus.payloads, self.corpus.ts, self.Record, self.source.produce)
        for i in range(lo, hi):
            produce(Record(key=None, value=payloads[i], timestamp=ts[i]))

    def _drain_locked(self, max_records: int) -> None:
        while self.engine.poll_once(max_records=max_records) or self.ex.pending_records():
            pass

    def consumed(self) -> int:
        return sum(v for (tn, _), v in self.handle.consumer.positions.items()
                   if tn == self.corpus.source_topic)

    def check_running(self, when: str) -> None:
        errors = [(w, m) for w, m in list(self.engine.processing_log)
                  if not w.startswith(_PLOG_INFORMATIONAL)]
        if self.handle.state != "RUNNING" or errors or self.engine.fallback_reasons:
            raise HarnessError(
                f"query not healthy {when}: state {self.handle.state}, "
                f"errors {errors[:3]}, fallbacks {dict(self.engine.fallback_reasons)}")

    def _quiescent(self, produced: int) -> bool:
        """Every produced event consumed and answered.  The cheap look first;
        then the same look with the server's engine lock in hand: a tick
        holds that lock from its poll to its last sink record, so no tick is
        half-way (between a poll that has advanced the offsets and the
        first record handed on, both counters read as done)."""
        if self.consumed() < produced or self.ex.pending_records():
            return False
        with self.srv.engine_lock:
            return self.consumed() >= produced and self.ex.pending_records() == 0

    def wait_quiet(self, produced: int, limit_s: float) -> float:
        """Wait until every produced event is consumed, nothing is pending
        in the executor and the sink has stopped growing; returns the
        instant the sink last grew."""
        deadline = time.perf_counter() + limit_s
        last, since = -1, time.perf_counter()
        while True:
            size = sum(self.sink.end_offsets())
            now = time.perf_counter()
            if size != last:
                last, since = size, now
            if (now - since > 0.3 and self._quiescent(produced)
                    and sum(self.sink.end_offsets()) == size):
                return since
            if now > deadline:
                raise HarnessError(
                    f"not quiet after {limit_s}s: produced {produced}, "
                    f"consumed {self.consumed()}, pending {self.ex.pending_records()}")
            if self.handle.state != "RUNNING":
                self.check_running("while draining")
            time.sleep(0.01)

    # ---- the window --------------------------------------------------
    def window(self, traffic: Optional[Dict[str, Any]] = None,
               seconds: Optional[float] = None) -> None:
        """One measured window of ``traffic`` (the cell's own unless given),
        from the first event not yet offered."""
        from generator import Producer, SinkTail

        traffic = self.traffic if traffic is None else traffic
        seconds = float(self.args.seconds) if seconds is None else seconds
        lo = self.win_lo = self.n_events
        hi = min(len(self.corpus.payloads), lo + window_events(traffic, seconds))
        Record = self.Record
        producer = Producer(
            traffic, self.source,
            lambda value, ts: Record(key=None, value=value, timestamp=ts),
            self.corpus, lo, hi, self.consumed)
        tail = SinkTail(self.sink, float(traffic.get("tail_every_ms", 2.0)))
        self.sink_start = list(self.sink.end_offsets())
        if self.before_window is not None:
            self.before_window(self)
        spans_before = stage_totals(self.recorder)
        ticklog = TickLog(self.recorder) if self.args.trace else None
        gclog = GcLog()
        gclog.start()
        tail.start()
        self.setup_s = time.perf_counter() - T_PROCESS
        producer.start()
        while producer.t0 is None:
            time.sleep(0.0005)
        t0 = producer.t0
        say("window_open", setup_s=round(self.setup_s, 3), seconds=seconds,
            jit_miss=spans_before.get("device.compile", {}).get("jit_miss", 0.0))
        trace = None
        if self.args.trace:
            trace = self._traced_part(t0, seconds)
        backlog: List[int] = [producer.produced - self.consumed()]
        while producer.is_alive() and time.perf_counter() < t0 + seconds:
            time.sleep(0.1)
            backlog.append(producer.produced - self.consumed())
        producer.stop_event.set()
        producer.join(30.0)
        t1 = time.perf_counter()
        spans_after = stage_totals(self.recorder)
        self.obs.update(gclog.stop())
        if producer.error is not None:
            raise HarnessError(f"generator failed: {producer.error!r}")
        self.offered = producer.produced - lo
        # every answer due is waited for, a minute past the close if need be
        self.wait_quiet(producer.produced, 60.0 + seconds)
        tail.stop_event.set()
        tail.join(5.0)
        if ticklog is not None:
            ticklog.close()
        self.peak_bytes = memory_peak(self.jax)
        self.check_running("after the window")
        self.n_events = producer.produced
        self._reduce(producer, tail, t0, t1, seconds, spans_before, spans_after)
        half = backlog[len(backlog) // 2:]
        self.obs["backlog.rows_max"] = float(max(backlog))
        self.obs["backlog.rows_end"] = float(backlog[-1])
        self.obs["backlog.rows_second_half_mean"] = float(sum(half) / len(half))
        if trace is not None:
            self._reduce_trace(trace, ticklog)

    def _traced_part(self, t0: float, seconds: float):
        """Profile ``trace_seconds`` from the middle of the window."""
        from jax import profiler

        want = min(float(self.traffic.get("trace_seconds", 4.0)), seconds * 0.6)
        start = t0 + max(0.0, (seconds - want) / 2)
        trace_dir = os.path.join(ROOT, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        _sleep_until(start)
        profiler.start_trace(trace_dir, profiler_options=opts)
        anchor = time.perf_counter()
        with profiler.TraceAnnotation(f"bench_anchor#{anchor!r}"):
            pass
        _sleep_until(anchor + want)
        end = time.perf_counter()
        with profiler.TraceAnnotation(f"bench_anchor_end#{end!r}"):
            pass
        profiler.stop_trace()
        say("traced", seconds=round(end - anchor, 3),
            stop_trace_seconds=round(time.perf_counter() - end, 2))
        return trace_dir

    # ---- from observations to numbers --------------------------------
    def _reduce(self, producer, tail, t0, t1, seconds, spans_before, spans_after) -> None:
        import numpy as np

        obs = self.obs
        obs.update(stage_deltas(spans_before, spans_after))
        jit_miss = obs.get("span.device.compile.jit_miss", 0.0)
        say("window_closed", offered=self.offered, exhausted=producer.exhausted,
            jit_miss_in_window=jit_miss, generator_seconds=round(t1 - t0, 3))
        self.numbers["compiles_in_window"] = {"value": jit_miss, "limit": 0}
        if producer.exhausted and t1 - t0 < seconds * 0.99:
            raise HarnessError(
                "the corpus ran out before the window closed: raise "
                "window_events_per_s in the traffic file")

        # the window's result records, each with the instant it became
        # readable and the event it is the result of
        records: List[Tuple[Any, Any, Any]] = []
        readable: List[Any] = []
        ends = self.sink.end_offsets()
        for p, (a, b) in enumerate(zip(self.sink_start, ends)):
            recs = self.sink.read(p, a, b - a)
            records.extend((r.key, r.window, r.value) for r in recs)
            readable.append(tail.readable_at(p, b - a, a))
        readable = np.concatenate(readable) if readable else np.zeros(0)
        lo = self.win_lo
        event = self.dep.result_event_index(self.corpus, lo, producer.produced, records)
        known = (event >= 0) & ~np.isnan(readable)
        self.numbers["results_unplaced"] = {
            "value": int(len(records) - known.sum()), "limit": 0}
        due, sent = producer.due_and_sent()
        obs["sink.records"] = float(len(records))
        obs["window.offered"] = float(self.offered)
        obs["window.span_seconds"] = t1 - t0

        # throughput: every event consumed *and* answered on the sink by
        # the window's close, over the whole window's seconds
        inside = known & (readable <= t0 + seconds)
        obs["window.events"] = float(int(event[inside].max()) + 1 - lo) if inside.any() else 0.0
        obs["window.seconds"] = seconds
        # latency: due -> readable, every result record of an event that
        # was due inside the window
        due_of = np.full(len(records), np.inf)
        due_of[known] = due[event[known] - lo]
        lat = (readable - due_of) * 1e3
        in_window = due_of < t0 + seconds
        if in_window.sum() >= 20:
            q = np.percentile(lat[in_window], [50, 95, 99])
            obs.update({"latency.p50_ms": float(q[0]), "latency.p95_ms": float(q[1]),
                        "latency.p99_ms": float(q[2]), "latency.samples": float(in_window.sum())})
        # what a shorter window of this same run would have read
        for part in (p for p in (10.0, 20.0, 30.0, 40.0) if p < seconds):
            ans, due_in = known & (readable <= t0 + part), due_of < t0 + part
            if ans.any() and due_in.sum() >= 20:
                say("window_prefix", seconds=part,
                    events_per_s=round((int(event[ans].max()) + 1 - lo) / part, 1),
                    **{f"latency_p{q}_ms": round(float(np.percentile(lat[due_in], q)), 2)
                       for q in (50, 95)})
        # the instants at which answers became readable, tick by tick
        bursts = np.unique(readable[known])
        if len(bursts) > 3:
            gaps_ms = np.diff(bursts[np.r_[True, np.diff(bursts) > 0.05]]) * 1e3
            if len(gaps_ms):
                say("answer_intervals_ms", n=len(gaps_ms),
                    **{f"p{q}": round(float(np.percentile(gaps_ms, q)), 1)
                       for q in (10, 50, 90, 99)}, max=round(float(gaps_ms.max()), 1))
        late = (sent - due) * 1e3
        if len(late):
            obs["generator.late_ms_p95"] = float(np.percentile(late, 95))
        obs["memory.peak_bytes"] = float(self.peak_bytes)
        say("window_spans", **{k[5:]: round(v, 1) for k, v in obs.items()
                               if k.startswith("span.") and k.endswith((".total_ms", ".n", ".rows"))})
        say("window_numbers", **{k: round(v, 4) for k, v in obs.items()
                                 if k.startswith(("window.", "latency.", "generator.", "sink.", "gc."))})

    def _reduce_trace(self, trace_dir: str, ticklog) -> None:
        import trace_reduce
        import work_bytes

        t = time.perf_counter()
        if self.args.keep_trace:
            os.makedirs(self.args.keep_trace, exist_ok=True)
            shutil.copy(trace_reduce.newest_xplane(trace_dir), self.args.keep_trace)
        red = trace_reduce.reduce_dir(trace_dir, ticklog.spans)
        shutil.rmtree(trace_dir, ignore_errors=True)
        say("trace_reduced", seconds=round(time.perf_counter() - t, 2),
            **{k: red[k] for k in ("window_s", "busy_s", "devices", "top_module", "aligned")})
        self.obs.update(red["obs"])
        self.device_trace = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        self.breakdown = red["breakdown"]
        peaks = work_bytes.peaks_for(self.device["kind"], rehearse=self.args.rehearse)
        self.obs.update({f"peak.{k}": float(v) for k, v in peaks.items()
                         if isinstance(v, (int, float))})
        ticks = self.obs.get("span.poll.n", 0.0)
        if ticks:
            self.obs["work.step_min_bytes"] = float(work_bytes.step_min_bytes(
                self.config["work_bytes"],
                rows_in=self.obs.get("span.poll.rows", 0.0) / ticks,
                rows_out=self.obs["sink.records"] / ticks))

    # ---- the comparison ----------------------------------------------
    def compare(self) -> None:
        t = time.perf_counter()
        records = [(r.key, r.window, r.value) for r in self.sink.all_records()]
        store = self.dep.read_store(self.ex)
        pulls = [
            (key, self.dep.read_pull(post(self.srv.url, "/query", {"ksql": sql})))
            for key, sql in self.dep.pull_queries(
                self.corpus, self.n_events, self.args.seed,
                int(self.config["sizes"]["pull_lookups"]))
        ]
        say("answers_read", sink_records=len(records), store=store, pulls=len(pulls),
            seconds=round(time.perf_counter() - t, 2))
        self.close()
        t = time.perf_counter()
        if self.args.control:
            program = self.dep.compare(self.corpus, self.n_events, records, store, pulls)
            say("program_numbers", correct=within(program), numbers=program)
            records = self.dep.control_reference(
                self.corpus, self.n_events, self.args.control, self.args.seed)
            store, pulls = None, None
        self.numbers.update(self.dep.compare(
            self.corpus, self.n_events, records, store, pulls))
        say("compared", seconds=round(time.perf_counter() - t, 2))

    def close(self) -> None:
        if getattr(self, "srv", None) is not None:
            # daemon-thread XLA teardown aborts the process otherwise
            self.srv.stop()
            self.srv = None


def window_events(traffic: Dict[str, Any], seconds: float) -> int:
    """Events the corpus holds for the window: what the mix can offer in
    ``seconds`` (a paced mix: exactly its rate)."""
    if traffic["mode"] == "paced":
        return int(float(traffic["rate_events_per_s"]) * seconds) + 1
    return int(float(traffic["window_events_per_s"]) * seconds)


def within(numbers: Dict[str, Dict[str, float]]) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


# ------------------------------------------------------------ the metrics
def end_to_end_values(run: Run) -> Dict[str, Optional[float]]:
    o = run.obs
    return {
        "events_per_s": o["window.events"] / o["window.seconds"] or None,
        "latency_p50_ms": o.get("latency.p50_ms"),
        "latency_p95_ms": o.get("latency.p95_ms"),
        "setup_s": run.setup_s,
    }


def result_line(run: Run) -> Dict[str, Any]:
    import metric_reader

    cell = run.cell["name"]
    metrics: Dict[str, Dict[str, Any]] = {}
    if run.args.trace:
        for m in run.bench["per_layer"]:
            if applies(m, cell):
                value = metric_reader.read(
                    load_json(HERE, "layer_metrics", m["name"] + ".json"), run.obs)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end_values(run)
        for m in run.bench["end_to_end"]:
            if applies(m, cell):
                if values.get(m["name"]) is None:
                    raise HarnessError(f"end-to-end metric {m['name']} has no reading")
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device = dict(run.device, memory_peak_bytes=run.peak_bytes)
    if run.args.trace:
        device.update(run.device_trace)
    failed = int(run.numbers.get("sink_events_missing", {}).get("value", 0))
    line: Dict[str, Any] = {
        "correct": within(run.numbers),
        "attempted": int(run.offered),
        "failed": min(failed, int(run.offered)),
        "metrics": metrics,
        "device": device,
    }
    if run.args.rehearse:
        line["rehearsal"] = True
    if run.args.control:
        line["control"] = run.args.control
    if run.breakdown is not None:
        line["breakdown"] = run.breakdown
    line["compared"] = run.numbers
    return line


# ------------------------------------------------------------------ main
def parse_args(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX has; never "
                         "reports a tpu it did not run on")
    ap.add_argument("--control", default="",
                    help="after the run, put the reference with this "
                         "guarantee broken in the program's place: the "
                         "result must read correct=false")
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's .xplane.pb into this "
                         "directory before it is reduced and deleted")
    return ap.parse_args(argv)


def open_run(args: argparse.Namespace) -> Tuple[int, Optional[Run]]:
    """Load the cell, place the compile cache, look for the chip; returns
    (exit code, run) with the run None where it cannot start."""
    try:
        bench, cell, config, traffic = load_cell(args.workload, args.rehearse)
        from ksql_tpu.runtime import compile_cache
    except (HarnessError, ImportError, OSError) as e:
        print(f"benchmark: cannot start: {e!r}", file=sys.stderr)
        return 3, None
    cache_dir = compile_cache.place()
    import jax

    if args.rehearse:
        # tiny programs compile under JAX's floor for the persistent cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = device_facts(jax)
    if not args.rehearse and device["platform"] != "tpu":
        print(f"benchmark: no TPU (jax.devices()[0].platform is "
              f"{device['platform']!r}); --rehearse runs without one", file=sys.stderr)
        return 2, None
    if device["count"] < int(cell["chips"]):
        print(f"benchmark: {args.workload} needs {cell['chips']} chips, "
              f"jax.devices() has {device['count']}", file=sys.stderr)
        return 2, None
    say("start", workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device, compile_cache=cache_dir,
        rehearsal=args.rehearse, config=cell["config"], traffic=cell["traffic"])
    run = Run(args, bench, cell, config, traffic,
              load_deployment(config["deployment"]), jax)
    run.device = device
    return 0, run


def run_cell(args: argparse.Namespace,
             before_window: Optional[Callable[[Run], None]] = None,
             ) -> Tuple[int, Optional[Dict[str, Any]]]:
    """Drive one run; returns (exit code, result line)."""
    code, run = open_run(args)
    if run is None:
        return code, None
    run.before_window = before_window
    try:
        run.setup()
        run.window()
        run.compare()
        line = result_line(run)
    except HarnessError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1, None
    finally:
        run.close()
    for name, n in line["compared"].items():
        print(f"COMPARED {name} value={n['value']} limit={n['limit']}", file=sys.stderr)
    print(f"COMPARED correct={line['correct']}", file=sys.stderr, flush=True)
    return 0, line


def main(argv: Optional[Sequence[str]] = None) -> int:
    code, line = run_cell(parse_args(argv))
    if line is not None:
        print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
