"""From a profiler trace (``.xplane.pb``) to device busy/idle time, time per
XLA module, the operations that took most time and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else.  What is a device:
a plane named ``/device:TPU:<n>`` (any ``/device:`` plane).  On such a
plane the line ``XLA Ops`` holds one event per executed operation and the
line ``XLA Modules`` one per executed program.  Off the chip (a rehearsal
on the CPU backend) there is no device plane; the host plane's events that
carry an ``hlo_module`` stat stand in, so that the same code runs — their
numbers are never reported as a chip's.

The traced window is what lies between the harness's two anchors
(``bench_anchor#<perf_counter>`` and ``bench_anchor_end#...``, host
``TraceAnnotation``s whose names carry the host clock), which also align
the program's own spans with the trace: an idle gap of the device is named
after the flight-recorder span that covers most of it.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ANCHOR, ANCHOR_END = "bench_anchor#", "bench_anchor_end#"
#: a gap shorter than this lies between two operations of one program; it
#: is counted as idle but not looked up among the host's spans
SHORT_GAP_NS = 20_000.0

Interval = Tuple[float, float]


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clipped(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps(cover: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``cover`` (sorted, disjoint, inside lo..hi) leaves of lo..hi."""
    out, at = [], lo
    for a, b in cover:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def _anchors(planes) -> Tuple[Optional[Tuple[float, float]], Optional[float]]:
    """((trace ns, perf_counter s) of the first anchor, trace ns of the end
    anchor), from the host planes."""
    first, end = None, None
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(ANCHOR_END):
                    end = float(e.start_ns)
                elif name.startswith(ANCHOR):
                    first = (float(e.start_ns), float(name[len(ANCHOR):]))
    return first, end


def _device_lines(planes):
    """[(device name, op events, module events)]."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue  # a plane of the chip that runs no XLA operation
        ops = _events(lines[OPS_LINE])
        modules = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        if ops:
            out.append((plane.name, ops, modules))
    if out:
        return out, True
    # no device plane: the CPU backend of a rehearsal
    ops, modules = [], {}
    for plane in planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_module" in stats:
                    a, b = float(e.start_ns), float(e.start_ns) + float(e.duration_ns)
                    ops.append((e.name, a, b))
                    modules.setdefault((stats["hlo_module"], stats.get("program_id")), []).append((a, b))
    mods = []
    for (name, _pid), spans in modules.items():
        # one "module event" per burst of its operations
        for a, b in union((a - 1e5, b + 1e5) for a, b in spans):
            mods.append((str(name), a + 1e5, b - 1e5))
    return ([("host-backend", ops, mods)] if ops else []), False


def reduce_file(path: str,
                host_spans: Sequence[Tuple[str, float, float, int]] = ()) -> Dict[str, Any]:
    """``host_spans``: the program's spans as (name, start s, end s, depth)
    on ``perf_counter``'s clock."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    devices, on_device = _device_lines(planes)
    if not devices:
        raise ValueError(f"{path}: no operation ran on a device in the traced window")
    anchor, end_ns = _anchors(planes)
    every = [iv for _, ops, _ in devices for iv in ((a, b) for _, a, b in ops)]
    lo = anchor[0] if anchor else min(a for a, _ in every)
    hi = end_ns if end_ns and end_ns > lo else max(b for _, b in every)
    window_s = (hi - lo) / 1e9

    busy, covers = [], []
    for _name, ops, _mods in devices:
        cover = union(clipped(((a, b) for _, a, b in ops), lo, hi))
        covers.append(cover)
        busy.append(sum(b - a for a, b in cover) / 1e9)
    fullest = max(range(len(busy)), key=busy.__getitem__)

    # time per module (all devices: a mesh's program runs on each) and the
    # module that took most of it: the step
    per_module: Dict[str, List[float]] = {}
    for _name, _ops, mods in devices:
        for name, a, b in mods:
            if b > lo and a < hi:
                st = per_module.setdefault(_module_name(name), [0.0, 0])
                st[0] += (min(b, hi) - max(a, lo)) / 1e6
                st[1] += 1
    obs: Dict[str, float] = {
        "trace.window_s": window_s,
        "trace.busy_s": sum(busy) / len(busy),
        "trace.busy_s_fullest": busy[fullest],
        "trace.devices": float(len(devices)),
        "trace.idle_pct": 100.0 * (1.0 - busy[fullest] / window_s),
    }
    top_module = None
    if per_module:
        top_module = max(per_module, key=lambda m: per_module[m][0])
        total_ms, calls = per_module[top_module]
        obs["trace.step.total_ms"] = total_ms / len(devices)
        obs["trace.step.calls"] = calls / len(devices)
    for name, (total_ms, calls) in per_module.items():
        obs[f"trace.module.{name}.total_ms"] = total_ms / len(devices)
        obs[f"trace.module.{name}.calls"] = calls / len(devices)

    # the operations that took most time, on the fullest device
    per_op: Dict[str, float] = {}
    for name, a, b in devices[fullest][1]:
        if b > lo and a < hi:
            name = _op_name(name)
            per_op[name] = per_op.get(name, 0.0) + (min(b, hi) - max(a, lo)) / 1e9
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]

    # idle gaps of the fullest device, named after the host span that
    # covers most of each
    named: Dict[str, float] = {}
    aligned = anchor is not None and bool(host_spans)
    spans_ns = []
    if aligned:
        ns0, perf0 = anchor
        spans_ns = [(name, ns0 + (a - perf0) * 1e9, ns0 + (b - perf0) * 1e9, depth)
                    for name, a, b, depth in host_spans]
    for a, b in gaps(covers[fullest], lo, hi):
        if b - a < SHORT_GAP_NS:
            shares = {"between_ops": b - a}
        elif aligned:
            shares = _covering(spans_ns, a, b)
        else:
            shares = {"host": b - a}
        for name, ns in shares.items():
            named[name] = named.get(name, 0.0) + ns / 1e9
    idle_gaps = sorted(named.items(), key=lambda kv: -kv[1])[:10]

    return {
        "window_s": window_s,
        "busy_s": obs["trace.busy_s"],
        "devices": len(devices),
        "on_device": on_device,
        "top_module": top_module,
        "aligned": aligned,
        "obs": obs,
        "breakdown": {
            "device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": [[n, s] for n, s in idle_gaps],
        },
    }


def reduce_dir(trace_dir: str, host_spans=()) -> Dict[str, Any]:
    return reduce_file(newest_xplane(trace_dir), host_spans)


def _module_name(event_name: str) -> str:
    """``jit__trace_step(1234567)`` -> ``jit__trace_step``: the program's
    name without the fingerprint the trace appends."""
    return event_name.split("(", 1)[0].strip()


def _op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the trace names
    an operation by its whole HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _covering(spans_ns, a: float, b: float) -> Dict[str, float]:
    """How a..b divides among the host's spans: every instant goes to the
    deepest span that covers it, and to ``between_ticks`` where none does.
    Returns nanoseconds by span name."""
    left: List[Interval] = [(a, b)]
    out: Dict[str, float] = {}
    for name, sa, sb, _depth in sorted(
            (s for s in spans_ns if s[2] > a and s[1] < b), key=lambda s: -s[3]):
        rest: List[Interval] = []
        for x, y in left:
            lo, hi = max(x, sa), min(y, sb)
            if hi <= lo:
                rest.append((x, y))
                continue
            out[name] = out.get(name, 0.0) + hi - lo
            if x < lo:
                rest.append((x, lo))
            if hi < y:
                rest.append((hi, y))
        left = rest
        if not left:
            break
    if left:
        out["between_ticks"] = sum(y - x for x, y in left)
    return out
