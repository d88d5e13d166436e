#!/usr/bin/env python3
"""Device seconds per operator scope, from a profiler trace.

    python3 benchmark/scope_times.py <file.xplane.pb>

The step program names its operators with ``jax.named_scope``
(``probe_insert``, ``scatter_combine``, ``emit_compact``, ...).  XLA keeps
the scope path in each operation's ``op_name``, and the chip's trace
carries it as the ``tf_op`` stat of the operation's *event metadata*
(``jit(_trace_step)/probe_insert/while/body/gather``), which
``jax.profiler.ProfileData`` does not show: it lists an event's own stats
only.  So this tool reads the file through the ``xplane_pb2`` bindings that
TensorFlow ships (imported here alone; the harness never needs them).

An operation's time is its *self* time: events of the ``XLA Ops`` line
nest (a ``while`` covers the operations of its body), and every instant
goes to the innermost event that covers it, so the scopes add up to the
device's busy time and the children of a ``while`` are counted once.

Wired into nothing: ``trace_reduce.py`` names the ledger's ``device_ops``
by HLO instruction (``while.8``); grouping them by scope is that file's
next edit, and ``scope_of`` and ``self_times`` are what it needs.
"""

from __future__ import annotations

import re
import sys
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
#: event-metadata stats that may carry the scope path, in order of trust
SCOPE_STATS = ("tf_op", "hlo_op", "long_name")
#: components of an op_name that JAX's transforms and control flow add
_NOT_A_SCOPE = re.compile(
    r"^(jit|pjit|xla_call|while|cond|body|scan|branch_\d+|closed_call|"
    r"core_call|remat\d*|checkpoint|custom_jvp|custom_vjp|shard_map|"
    r"vmap|transpose|jvp)(\(.*\))?$")
NO_SCOPE = "(no scope)"


def device_planes(path: str) -> list:
    """The XPlanes of the file's devices that hold an ``XLA Ops`` line."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    with open(path, "rb") as f:
        space = xplane_pb2.XSpace.FromString(f.read())
    return [p for p in space.planes if p.name.startswith("/device:")
            and any(ln.name == OPS_LINE and ln.events for ln in p.lines)]


def op_events(plane) -> List[Tuple[int, int, int]]:
    """(event metadata id, start ps, duration ps) of the plane's operations."""
    return [(ev.metadata_id, ln.timestamp_ns * 1000 + ev.offset_ps, ev.duration_ps)
            for ln in plane.lines if ln.name == OPS_LINE for ev in ln.events]


def scope_stat(plane, metadata_id: int) -> Tuple[str, str]:
    """(name of the stat that carries an operation's op_name, the op_name),
    or ("none", "") where the event metadata has no such stat."""
    stats = {}
    for st in plane.event_metadata[metadata_id].stats:
        name = plane.stat_metadata[st.metadata_id].name
        # an interned string is a reference to another stat metadata's name
        stats[name] = (st.str_value if st.WhichOneof("value") == "str_value"
                       else plane.stat_metadata[st.ref_value].name
                       if st.WhichOneof("value") == "ref_value" else "")
    return next(((s, stats[s]) for s in SCOPE_STATS if "/" in stats.get(s, "")),
                ("none", ""))


# ------------------------------------------------------------- the scopes
def scope_of(op_name: str) -> str:
    """``jit(_trace_step)/probe_insert/while/body/gather`` ->
    ``probe_insert``: the named scopes of an op_name, without what JAX's
    transforms add and without the primitive at its end."""
    parts = [p for p in op_name.split("/")[:-1] if not _NOT_A_SCOPE.match(p)]
    return "/".join(parts) or NO_SCOPE


def self_times(events: List[Tuple[int, int, int]]) -> Dict[int, int]:
    """Picoseconds by event metadata id, every instant given to the
    innermost event that covers it."""
    out: Dict[int, int] = {}
    stack: List[List[int]] = []  # [metadata id, end, start, covered by children]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            mid, end, start, covered = stack.pop()
            out[mid] = out.get(mid, 0) + (end - start) - covered
            if stack:
                stack[-1][3] += end - start

    for mid, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        end = start + dur
        if stack:
            end = min(end, stack[-1][1])  # a child never outlasts its parent
        stack.append([mid, end, start, 0])
    close(1 << 62)
    return out


def scope_seconds(path: str) -> Tuple[Dict[str, float], Dict[str, int]]:
    """(device seconds by scope, on the device that was busiest; how many
    distinct operations took their scope from each stat)."""
    best: Tuple[float, Dict[str, float], Dict[str, int]] = (-1.0, {}, {})
    for plane in device_planes(path):
        per_scope: Dict[str, float] = {}
        carried: Dict[str, int] = {}
        for mid, ps in self_times(op_events(plane)).items():
            stat, op_name = scope_stat(plane, mid)
            scope = scope_of(op_name) if op_name else NO_SCOPE
            carried[stat] = carried.get(stat, 0) + 1
            per_scope[scope] = per_scope.get(scope, 0.0) + ps / 1e12
        busy = sum(per_scope.values())
        if busy > best[0]:
            best = (busy, per_scope, carried)
    if best[0] < 0:
        raise ValueError(f"{path}: no device plane with an '{OPS_LINE}' line")
    return best[1], best[2]


def main(argv: List[str]) -> int:
    if len(argv) != 2:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    per_scope, carried = scope_seconds(argv[1])
    busy = sum(per_scope.values())
    print(f"device busy {busy:.6f} s; scope taken from: "
          + ", ".join(f"{k} ({n} ops)" for k, n in sorted(carried.items())))
    for scope, s in sorted(per_scope.items(), key=lambda kv: -kv[1]):
        print(f"{s:12.6f} s  {100 * s / busy:5.1f} %  {scope}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
