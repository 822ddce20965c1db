"""The trace reduction, on a small trace recorded on a TPU v5e: a Pallas
kernel step and a matmul step inside spans, with idle time between them.
``small.meta.json`` holds the capture's clock readings and spans."""
import json
from pathlib import Path

import pytest

from bench import spans as bspans
from bench import trace_reduce as tr
from bench.capture import ANCHOR

DATA = Path(__file__).resolve().parent.parent / "testdata"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "small.xplane.pb"))
    meta = json.loads((DATA / "small.meta.json").read_text())
    red = tr.reduce(pd, anchor=ANCHOR, anchor_perf=meta["anchor_perf"],
                    t0_perf=meta["t0"], t1_perf=meta["t1"],
                    spans=meta["spans"], spans_t0_perf=meta["tracer_t0"])
    return pd, meta, red


def _window_ns(pd, meta):
    a = tr.anchor_ns(pd, ANCHOR)
    return (a + round((meta["t0"] - meta["anchor_perf"]) * 1e9),
            a + round((meta["t1"] - meta["anchor_perf"]) * 1e9))


def test_busy_is_the_union_of_device_ops(recorded):
    pd, meta, red = recorded
    t0, t1 = _window_ns(pd, meta)
    shift = tr.device_shift_ns(pd)
    assert 0 < shift < 5_000_000           # the device clock lags ~1.4 ms
    (evs,) = tr.device_ops(pd).values()
    covered = set()
    for _, s, d, _ in evs:                  # brute force over 100 ns cells
        s += shift
        covered.update(range(max(s, t0) // 100, min(s + d, t1) // 100))
    assert red["busy_s"] == pytest.approx(len(covered) * 100 / 1e9,
                                          abs=2e-6)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] == pytest.approx(meta["t1"] - meta["t0"],
                                            abs=1e-6)


def test_kernel_time_is_the_custom_calls(recorded):
    pd, meta, red = recorded
    (evs,) = tr.device_ops(pd).values()
    kernels = [e for e in evs if e[3]]
    t0, t1 = _window_ns(pd, meta)
    shift = tr.device_shift_ns(pd)
    # the kernel ran inside the "kernel_step" span once the clocks agree
    assert all(t0 <= s + shift and s + shift + d <= t1
               for _, s, d, _ in kernels)
    assert kernels and all("custom-call" in e[0] for e in kernels)
    assert red["kernel_s"] == pytest.approx(
        sum(d for _, _, d, _ in kernels) / 1e9)
    assert 0 < red["kernel_s"] < red["busy_s"]


def test_idle_gaps_charged_to_the_spans_open(recorded):
    _, meta, red = recorded
    gaps = dict(red["breakdown"]["idle_gaps"])
    # the capture slept 30 ms inside "outer" with no device work
    assert gaps.get("outer", 0) >= 0.03
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


def test_span_self_times(recorded):
    _, meta, _ = recorded
    spans = meta["spans"]
    own = bspans.self_times(spans)
    outer = next(s for s in spans if s["name"] == "outer")
    kids = [s for s in spans if s["parent"] == outer["sid"]]
    assert {s["name"] for s in kids} == {"kernel_step", "matmul_step"}
    assert own[outer["sid"]] == pytest.approx(
        outer["dur"] - sum(s["dur"] for s in kids))
    assert own[outer["sid"]] >= 0.03
    assert bspans.total_self(spans, ("kernel_step",)) == pytest.approx(
        next(s["dur"] for s in kids if s["name"] == "kernel_step"))


def test_programs_start_after_their_enqueue(recorded):
    pd, _, _ = recorded
    shift = tr.device_shift_ns(pd)
    (evs,) = tr.device_ops(pd).values()
    enq = min(int(e.start_ns) + int(e.duration_ns) for p in pd.planes
              for ln in p.lines for e in ln.events
              if e.name == "DoEnqueueProgram")
    assert min(s for _, s, _, _ in evs) + shift >= enq


def test_exclusive_time_subtracts_nested_ops():
    evs = [("while", 0, 100, False), ("body", 10, 30, False),
           ("body", 50, 20, True), ("next", 120, 5, False)]
    assert tr.exclusive(evs) == [50, 30, 20, 5]
    assert tr.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    assert list(tr.gaps([(0, 20), (30, 40)], 0, 50)) == [(20, 30), (40, 50)]
