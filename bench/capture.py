"""A traced window: the profiler's device trace and the program's spans,
on one clock.

``with capture.window() as w:`` starts ``jax.profiler`` into the capture's
directory (device and host events, no Python function tracing), marks the
start with a ``TraceAnnotation`` whose host-clock time it notes, and
activates a ``repro.obs`` tracer. On exit it stops the
profiler; ``w.reduce()`` then gives the device busy time, the Mosaic
kernel time, the top device operations and the longest idle gaps by the
span the host was in (``bench.trace_reduce``).
"""
from __future__ import annotations

import shutil
import time
from contextlib import contextmanager
from pathlib import Path

ANCHOR = "bench_anchor"


class Traced:
    def __init__(self, tracer, trace_dir: Path):
        self.tracer = tracer
        self.trace_dir = trace_dir
        self.anchor_perf = 0.0
        self.t0 = 0.0                 # window start, host perf_counter
        self.t1 = 0.0

    @property
    def spans(self) -> list:
        return self.tracer.spans

    def reduce(self) -> dict:
        from bench import trace_reduce

        pd = trace_reduce.load(self.trace_dir)
        return trace_reduce.reduce(
            pd, anchor=ANCHOR, anchor_perf=self.anchor_perf,
            t0_perf=self.t0, t1_perf=self.t1,
            spans=self.spans, spans_t0_perf=self.tracer.t0)


class Capture:
    def __init__(self, trace_dir: Path):
        self.trace_dir = Path(trace_dir)

    @contextmanager
    def window(self):
        import jax
        from repro.obs import Tracer, use_tracer

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        tracer = Tracer()
        w = Traced(tracer, self.trace_dir)
        # no Python function tracing: an event per call made the serving
        # host's encode about 15x slower (76 ms a document against ~5)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(ANCHOR):
                w.anchor_perf = time.perf_counter()
            with use_tracer(tracer):
                w.t0 = time.perf_counter()
                yield w
                w.t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
