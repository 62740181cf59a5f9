"""What every cell shares: the per-layer readers, the comparison lines
and the final JSON line."""
from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

from harness import manifest


def say(tag: str, **fields: Any) -> None:
    """One line of information before the result line."""
    print(f"[{tag}] " + json.dumps(fields, default=float), flush=True)


def compare(name: str, value: float, limit: float) -> bool:
    """Print one compared number beside its limit; True when inside."""
    ok = value == value and value <= limit      # NaN fails
    say("correct", number=name, value=value, limit=limit, ok=ok)
    return ok


def read_layers(man: Dict[str, Any], cell_name: str, reported: List[str],
                obs: Dict[str, Any], device_metrics: bool = True) -> Dict[str, Any]:
    """Run the reader of every per-layer metric this cell reports.  A
    reader that finds nothing to read returns None and the metric is
    left out."""
    out: Dict[str, Any] = {}
    for m in manifest.metrics_of(man, "per_layer", cell_name, reported):
        reader = manifest.load_reader(m["name"])
        if not device_metrics and getattr(reader, "DEVICE", False):
            out[m["name"]] = "not measured"
            continue
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def final_line(correct: bool, attempted: int, failed: int,
               metrics: Dict[str, Any], device: Dict[str, Any],
               breakdown: Optional[Dict[str, Any]] = None) -> None:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
