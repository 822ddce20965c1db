"""Nestable trace spans with a zero-cost disabled path.

Stdlib-only and JAX-free: a span records two `time.perf_counter` reads
and a dict append — it never touches device values, so enabling a trace
cannot add device->host transfers or XLA compiles. Call sites are placed
at *existing* sync points (the `engine.device_get` counted fetch,
`engine.fetch`, `np.asarray` on served scores); async dispatch between
sync points is attributed to the span that owns the next sync, which is
the honest accounting for an async runtime.

The span tree mirrors the solver and serve loops::

    path > lambda_grid
         > lambda_point > screen_round
                        > restricted_solve > bucket_stream
                        > kkt_check        > bucket_stream
                        > point_finish
    serve > drain  > pack
          > encode        (from submit; parents under serve when nested)
          > score  > put
                   > launch > compile
                   > fetch
          > swap

Nesting is tracked per-thread: each thread keeps its own span stack, so
a serve thread and a solver thread never corrupt each other's parents.

Where JAX is already loaded, each span of an active tracer also opens a
`jax.profiler.TraceAnnotation` of its name on its thread (found through
`sys.modules`, never imported here): under a profiler session the spans
land in its trace, on its clock, beside the device's operations; without
one the annotation costs about a microsecond. A `compile` span is
recorded by `on_compile`, a JAX duration listener that a module which
imports JAX registers, from the time JAX measured for the compile.

With no active tracer, `span()` returns a shared `_NULL_SPAN` singleton
whose `__enter__`/`__exit__`/`set` are no-ops.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional

__all__ = ["COMPILE_EVENT", "Tracer", "get_tracer", "on_compile", "span",
           "use_tracer"]

#: JAX's monitoring event timing one backend compile or persistent-cache
#: load (``jax._src.dispatch.BACKEND_COMPILE_EVENT``)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` of ``name``, or None
    where JAX is not loaded."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


class _Span:
    """Context manager recording one timed span on `tracer`."""

    __slots__ = ("_tracer", "name", "args", "sid", "parent", "_t0", "_tid",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, object]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.sid = next(tracer._sid)
        self.parent: Optional[int] = None
        self._t0 = 0.0
        self._tid = 0
        self._ann = None

    def set(self, **kw: object) -> "_Span":
        """Attach result metadata (nnz, status, ...) to the open span."""
        self.args.update(kw)
        return self

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        stack = tracer._stack()
        self.parent = stack[-1].sid if stack else None
        self._tid = tracer._tid()
        stack.append(self)
        self._ann = _annotation(self.name)
        self._t0 = tracer.clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = self._tracer.clock()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._record(self, self._t0, t1 - self._t0, self._tid)
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def set(self, **kw: object) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


class Tracer:
    """Collects span records; thread-safe, append-only.

    Records are plain dicts (`name`, `ts`, `dur`, `tid`, `sid`,
    `parent`, `args`) with `ts`/`dur` in seconds relative to the
    tracer's construction — `repro.obs.export` turns them into Chrome
    trace events / JSONL / summaries.
    """

    def __init__(self, clock=time.perf_counter) -> None:
        self.clock = clock
        self.t0 = clock()
        self.spans: List[dict] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._sid = itertools.count(1)
        self._tids: Dict[int, int] = {}

    def _stack(self) -> List[_Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids)
            return tid

    def _record(self, sp: _Span, t0: float, dur: float, tid: int) -> None:
        rec = {
            "name": sp.name,
            "ts": t0 - self.t0,
            "dur": dur,
            "tid": tid,
            "sid": sp.sid,
            "parent": sp.parent,
            "args": sp.args,
        }
        with self._lock:
            self.spans.append(rec)

    def span(self, name: str, **args: object) -> _Span:
        return _Span(self, name, args)

    def record(self, name: str, dur: float, **args: object) -> None:
        """Record a span of ``dur`` seconds that ends now, on the calling
        thread, under the span open there (a compile JAX timed)."""
        t1 = self.clock()
        sp = _Span(self, name, args)
        stack = self._stack()
        sp.parent = stack[-1].sid if stack else None
        self._record(sp, t1 - dur, dur, self._tid())

    def wall_s(self) -> float:
        """Wall time covered so far: last span end (or now if none)."""
        with self._lock:
            if not self.spans:
                return self.clock() - self.t0
            return max(r["ts"] + r["dur"] for r in self.spans)


_ACTIVE: Optional[Tracer] = None
_ACTIVE_LOCK = threading.Lock()


def get_tracer() -> Optional[Tracer]:
    return _ACTIVE


def span(name: str, **args: object):
    tracer = _ACTIVE
    return _NULL_SPAN if tracer is None else tracer.span(name, **args)


def on_compile(event: str, duration: float, **kw: object) -> None:
    """JAX duration listener: a backend compile or persistent-cache load
    (``COMPILE_EVENT``) becomes a ``compile`` span of the active tracer,
    on the thread that compiled and so under the span that caused it."""
    tracer = _ACTIVE
    if tracer is not None and event == COMPILE_EVENT:
        tracer.record("compile", duration, fun=kw.get("fun_name"))


@contextmanager
def use_tracer(tracer: Optional[Tracer]) -> Iterator[None]:
    """Activate `tracer` for the enclosed block (re-entrant: the prior
    active tracer is restored on exit). Pass None to force-disable."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        prev, _ACTIVE = _ACTIVE, tracer
    try:
        yield
    finally:
        with _ACTIVE_LOCK:
            _ACTIVE = prev
