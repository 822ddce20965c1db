"""Self time of the program's ``repro.obs`` spans.

A span's self time is its duration less the part its child spans cover
(children nest inside their parent on one thread, so the sum of their
durations is that part).
"""
from __future__ import annotations

from typing import Dict, Iterable


def self_times(spans: Iterable[dict]) -> Dict[int, float]:
    """``{span id: self seconds}``."""
    spans = list(spans)
    child = {}
    for sp in spans:
        if sp["parent"] is not None:
            child[sp["parent"]] = child.get(sp["parent"], 0.0) + sp["dur"]
    return {sp["sid"]: sp["dur"] - child.get(sp["sid"], 0.0) for sp in spans}


def total_self(spans: Iterable[dict], names) -> float:
    """Summed self seconds of the spans named in ``names``."""
    spans = list(spans)
    own = self_times(spans)
    return sum(own[sp["sid"]] for sp in spans if sp["name"] in names)


def mean_ms(spans: Iterable[dict], name: str, *, live_only: bool = False):
    """Mean duration of the spans ``name`` in ms, or None if there are
    none. ``live_only`` skips drains that drained nothing."""
    durs = [sp["dur"] for sp in spans if sp["name"] == name
            and (not live_only or sp["args"].get("drained", 1))]
    return 1e3 * sum(durs) / len(durs) if durs else None
