"""Reads one per-layer metric from a run's observations.

A per-layer metric is a data file, ``benchmark/layer_metrics/<name>.json``:

    {"layer": "...", "unit": "ms", "moves": "events_per_s",
     "source": "program_span",
     "num": ["span.device.execute.total_ms"], "den": ["span.device.execute.n"],
     "scale": 1.0, "what": "one line on what the number is"}

Its value is ``scale * product(num) / product(den)`` over the run's flat
dictionary of observations (``benchmark/README.md`` lists their names).  A
metric whose observations are not all there, or whose denominator is 0,
has nothing to read: the reader returns None and the harness leaves the
metric out of the line.  It never returns 0 for a share.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional


def read(spec: Dict[str, Any], obs: Dict[str, float]) -> Optional[float]:
    value = float(spec.get("scale", 1.0))
    for name in spec["num"]:
        if name not in obs:
            return None
        value *= obs[name]
    for name in spec.get("den", []):
        if not obs.get(name):
            return None
        value /= obs[name]
    if not math.isfinite(value):
        return None
    if spec.get("unit") == "%" and value <= 0.0:
        return None
    return value
