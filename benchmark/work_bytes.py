"""The least bytes a device step's *work* has to move, and the chip's peaks.

Counted from rows and schema, never from the implementation: whatever
implements the step (XLA gathers and scatters today, a Pallas kernel
later) is held against the same number.  A configuration states its
schema's widths in its file under ``work_bytes``:

    {"per_input_row": {"key": 8, "event_time": 8, "slot_key_read": 16,
                       "aggregate_read": 8, "aggregate_write": 8},
     "per_emitted_row": {"key": 8, "window": 8, "value": 8}}

Each entry is a number of bytes that any implementation must read or write
in HBM once for that row.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def step_min_bytes(widths: Dict[str, Dict[str, int]], rows_in: float,
                   rows_out: float) -> float:
    """Least HBM bytes for one step that takes ``rows_in`` valid rows and
    emits ``rows_out``."""
    if rows_in < 0 or rows_out < 0:
        raise ValueError("row counts are not negative")
    per_in = sum(int(v) for v in widths["per_input_row"].values())
    per_out = sum(int(v) for v in widths["per_emitted_row"].values())
    return rows_in * per_in + rows_out * per_out


def peaks_for(device_kind: str, rehearse: bool = False) -> Dict[str, Any]:
    """The published peaks of ``device_kind``.  A kind that is not in
    ``peaks.json`` is an error, not a default; a rehearsal off the chip
    reads no peak at all (so no share of one is reported)."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind in table:
        return table[device_kind]
    if rehearse:
        return {}
    raise KeyError(
        f"device kind {device_kind!r} is not in benchmark/peaks.json "
        f"(known: {sorted(table)})")
