"""Profiler trace -> device busy time, kernel time, idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
``jax.profiler.ProfileData``:

* device planes are those named ``/device:TPU:<n>``; their operations are
  the events of the ``XLA Ops`` line, each named by its HLO instruction
  and the ``XLA Modules`` program it ran in;
* busy time is the union of those operations' intervals inside the
  window, per device, averaged over the devices that ran any;
* kernel (Mosaic) time is the summed duration of the operations that are
  custom calls to ``tpu_custom_call``; the top operations are ranked by
  their own time, less that of operations nested in them;
* the host clock is tied to the trace by one ``TraceAnnotation`` (the
  anchor) whose ``time.perf_counter()`` the capture noted, so the
  program's ``repro.obs`` spans and the window land on the trace's clock;
  the device's events are shifted onto the host's clock by the lead they
  show over their programs' enqueues (about 1.4 ms on a v5e);
* each idle gap inside the window is charged to the innermost span open
  at its midpoint (``no span`` where none is).
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def load(trace_dir):
    """The newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


_HLO = re.compile(r"^%?([^\s=]+) = .*?\b([a-z][\w\-]*)\(")


def is_kernel(name: str) -> bool:
    """A Mosaic kernel: a custom call to ``tpu_custom_call``."""
    return "tpu_custom_call" in name


def short_name(name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3 (fusion)``."""
    m = _HLO.match(name)
    return f"{m.group(1)} ({m.group(2)})" if m else name[:80]


def device_ops(pd) -> Dict[str, List[Tuple[str, int, int, bool]]]:
    """``{plane: [(module/op, start_ns, dur_ns, is_kernel)]}``, sorted."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        mods, evs = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = sorted((int(e.start_ns), int(e.start_ns)
                               + int(e.duration_ns), e.name.split("(")[0])
                              for e in line.events)
            elif line.name == OPS_LINE:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
        if not evs:
            continue
        starts = [m[0] for m in mods]
        named = []
        for name, s, d in sorted(evs, key=lambda e: (e[1], -e[2])):
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            named.append((f"{mod}/{short_name(name)}", s, d, is_kernel(name)))
        out[plane.name] = named
    return out


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def device_shift_ns(pd) -> int:
    """Shift that puts the device clock on the host's: each program can
    start on the device only after the host enqueued it
    (``DoEnqueueProgram``, matched by ``run_id``), so the shift is the
    largest lead of a program's start over its enqueue's end, and 0 where
    none leads."""
    enq, start = {}, {}
    for plane in pd.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if dev and line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if not dev and ev.name != "DoEnqueueProgram":
                    continue
                rid = _stat(ev, "run_id")
                if rid is None:
                    continue
                if dev:
                    start.setdefault(rid, int(ev.start_ns))
                else:
                    enq[rid] = int(ev.start_ns) + int(ev.duration_ns)
    leads = [enq[r] - start[r] for r in start if r in enq]
    return max([0] + leads)


def exclusive(evs) -> List[int]:
    """Each operation's time less that of the operations nested in it (a
    ``while`` holds its body's operations), for events sorted by start."""
    own = [d for _, _, d, _ in evs]
    stack: List[int] = []
    for i, (_, s, d, _) in enumerate(evs):
        while stack and s >= evs[stack[-1]][1] + evs[stack[-1]][2]:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return own


def anchor_ns(pd, name: str) -> int:
    """Start of the host event ``name`` (the capture's anchor)."""
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return int(ev.start_ns)
    raise ValueError(f"anchor {name!r} not in the trace")


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv, t0: int, t1: int):
    for s, e in iv:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            yield s, e


def gaps(busy: List[Tuple[int, int]], t0: int, t1: int):
    """The idle intervals of ``[t0, t1]`` between merged busy ones."""
    cur = t0
    for s, e in busy:
        if s > cur:
            yield cur, s
        cur = max(cur, e)
    if t1 > cur:
        yield cur, t1


class _Innermost:
    """Name of the shortest span open at a time: the span boundaries cut
    the clock into segments, each labelled once; a lookup bisects."""

    def __init__(self, spans_ns):
        self.cuts = sorted({t for s, e, _ in spans_ns for t in (s, e)})
        self.labels = []
        for a, b in zip(self.cuts, self.cuts[1:]):
            mid = (a + b) // 2
            best = min(((e - s, n) for s, e, n in spans_ns if s <= mid < e),
                       default=(0, "no span"))
            self.labels.append(best[1])

    def __call__(self, t: int) -> str:
        i = bisect.bisect_right(self.cuts, t) - 1
        return self.labels[i] if 0 <= i < len(self.labels) else "no span"


def reduce(pd, *, anchor: str, anchor_perf: float, t0_perf: float,
           t1_perf: float, spans: list = (), spans_t0_perf: float = 0.0
           ) -> dict:
    """Busy, kernel and idle readings of the window ``[t0, t1]`` (host
    ``perf_counter`` seconds)."""
    a_ns = anchor_ns(pd, anchor)

    def to_ns(t_perf: float) -> int:
        return a_ns + int(round((t_perf - anchor_perf) * 1e9))

    t0, t1 = to_ns(t0_perf), to_ns(t1_perf)
    shift = device_shift_ns(pd)
    per_plane = {k: [(n, s + shift, d, kern) for n, s, d, kern in evs]
                 for k, evs in device_ops(pd).items()}
    busy_ns, kernel_ns, op_ns = [], 0, {}
    merged_all = []
    for evs in per_plane.values():
        iv = list(_clip(((s, s + d) for _, s, d, _ in evs), t0, t1))
        if not iv:
            continue
        merged = union(iv)
        merged_all.append(merged)
        busy_ns.append(sum(e - s for s, e in merged))
        for (name, s, d, kern), own in zip(evs, exclusive(evs)):
            if s < t0 or s >= t1:
                continue
            kernel_ns += d if kern else 0
            op_ns[name] = op_ns.get(name, 0) + own
    n_dev = max(len(busy_ns), 1)
    spans_ns = [(to_ns(spans_t0_perf + sp["ts"]),
                 to_ns(spans_t0_perf + sp["ts"] + sp["dur"]), sp["name"])
                for sp in spans if sp["dur"] > 0]
    gap_by: Dict[str, int] = {}
    innermost = _Innermost(spans_ns)
    if merged_all:
        for s, e in gaps(merged_all[0], t0, t1):
            name = innermost((s + e) // 2)
            gap_by[name] = gap_by.get(name, 0) + (e - s)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gap_by.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "kernel_s": kernel_ns / n_dev / 1e9,
        "devices": len(busy_ns),
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in top_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in top_gaps],
        },
    }
