"""Tier-1 tests for ``repro.obs`` — registry, spans, exporters, report.

The fast lane runs this file: everything here is stdlib + tiny numpy
shapes except the integration tests at the bottom, which trace one tiny
real path solve and serve drain->score rounds to pin the wiring (span
tree shape, per-phase accounting, legacy-counter bit-identity, batch
ids, bytes, queue waits, compiles, the profiler's copy of the spans).
"""
import json
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.obs import (
    MetricsRegistry,
    ObsSession,
    Tracer,
    chrome_trace,
    observe,
    render_summary,
    summarize,
)
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.obs.registry import _NULL_COUNTER, _NULL_GAUGE, _NULL_HISTOGRAM
from repro.obs.report import main as report_main
from repro.obs.trace import _NULL_SPAN


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("hits") is c            # get-or-create
    reg.gauge("depth").set(7)
    assert reg.gauge("depth").value == 7.0
    h = reg.histogram("lat")
    for v in (0.001, 0.002, 0.004):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(0.007)


def test_labels_key_separate_instruments():
    reg = MetricsRegistry()
    reg.counter("faults", kind="swap").inc()
    reg.counter("faults", kind="kill").inc(2)
    snap = reg.collect()["counters"]
    assert snap["faults{kind=swap}"] == 1
    assert snap["faults{kind=kill}"] == 2


def test_value_returns_none_for_never_created():
    reg = MetricsRegistry()
    assert reg.value("nope") is None
    reg.counter("yes").inc()
    assert reg.value("yes") == 1


def test_histogram_percentiles_sane():
    h = MetricsRegistry().histogram("lat")
    vals = [i * 1e-3 for i in range(1, 101)]    # 1ms .. 100ms
    for v in vals:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["min"] == pytest.approx(1e-3)
    assert snap["max"] == pytest.approx(0.1)
    # log-bucketed interpolation: right order of magnitude, clamped range
    assert 0.02 <= snap["p50"] <= 0.08
    assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]


def test_empty_histogram_is_json_safe():
    snap = MetricsRegistry().histogram("lat").snapshot()
    assert snap == {"count": 0, "sum": 0.0, "min": None, "max": None,
                    "p50": None, "p95": None, "p99": None}
    json.dumps(snap)                            # no NaN anywhere


def test_callback_mirrors_legacy_dict_lazily():
    reg = MetricsRegistry()
    legacy = {"drained": 0}
    reg.register_callback("serve.batcher", lambda: legacy)
    legacy["drained"] = 9                       # mutate AFTER registration
    assert reg.collect()["callbacks"]["serve.batcher"] == {"drained": 9}


def test_dead_callback_does_not_kill_collect():
    reg = MetricsRegistry()
    reg.register_callback("bad", lambda: 1 / 0)
    out = reg.collect()["callbacks"]["bad"]
    assert "error" in out and "ZeroDivisionError" in out["error"]


def test_disabled_helpers_return_null_singletons():
    assert obs_registry.get_registry() is None
    assert obs_registry.counter("x") is _NULL_COUNTER
    assert obs_registry.gauge("x") is _NULL_GAUGE
    assert obs_registry.histogram("x") is _NULL_HISTOGRAM
    assert obs_trace.get_tracer() is None
    assert obs_trace.span("x") is _NULL_SPAN
    # all no-ops, no errors
    obs_registry.counter("x").inc()
    obs_registry.gauge("x").set(1)
    obs_registry.histogram("x").observe(0.1)
    with obs_trace.span("x") as sp:
        sp.set(k=1)
    obs_trace.on_compile(obs_trace.COMPILE_EVENT, 0.5, fun_name="jit(f)")


def test_use_registry_is_reentrant():
    outer, inner = MetricsRegistry(), MetricsRegistry()
    with obs_registry.use_registry(outer):
        obs_registry.counter("n").inc()
        with obs_registry.use_registry(inner):
            obs_registry.counter("n").inc(10)
        obs_registry.counter("n").inc()
    assert obs_registry.get_registry() is None
    assert outer.value("n") == 2 and inner.value("n") == 10


def test_counter_inc_is_thread_safe():
    reg = MetricsRegistry()
    c = reg.counter("n")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 4000


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def _fake_clock(times):
    it = iter(times)
    return lambda: next(it)


def test_spans_nest_and_record_parents():
    tr = Tracer(clock=_fake_clock([0.0, 1.0, 2.0, 3.0, 4.0]))
    with tr.span("outer", a=1) as outer:
        with tr.span("inner") as inner:
            inner.set(ok=True)
        outer.set(points=2)
    inner_rec, outer_rec = tr.spans          # completion order
    assert inner_rec["name"] == "inner" and inner_rec["args"] == {"ok": True}
    assert inner_rec["parent"] == outer_rec["sid"]
    assert outer_rec["parent"] is None
    assert outer_rec["args"] == {"a": 1, "points": 2}
    # rel to tracer start: construction ate tick 0, outer opened at 1
    assert outer_rec["ts"] == pytest.approx(1.0)
    assert outer_rec["dur"] == pytest.approx(3.0)
    assert inner_rec["dur"] == pytest.approx(1.0)
    assert tr.wall_s() == pytest.approx(4.0)


def test_sibling_threads_get_own_stacks():
    tr = Tracer()
    seen = {}

    def worker(name):
        with tr.span(name):
            pass

    with tr.span("main"):
        t = threading.Thread(target=worker, args=("side",))
        t.start()
        t.join()
    for r in tr.spans:
        seen[r["name"]] = r
    # the side thread's span must NOT have the main thread's span as
    # parent (stacks are thread-local) and gets its own small tid
    assert seen["side"]["parent"] is None
    assert seen["side"]["tid"] != seen["main"]["tid"]


# ---------------------------------------------------------------------------
# export + summary + report
# ---------------------------------------------------------------------------

def _toy_tracer():
    tr = Tracer(clock=_fake_clock([float(i) for i in range(20)]))
    with tr.span("path", path_len=2):
        with tr.span("lambda_point", index=0, lam=0.5) as sp:
            with tr.span("restricted_solve"):
                pass
            sp.set(nnz=3, status=0)
        with tr.span("lambda_point", index=1, lam=0.25) as sp:
            with tr.span("restricted_solve"):
                pass
            sp.set(nnz=5, status=0)
    return tr


def test_chrome_trace_events_are_complete_events():
    doc = chrome_trace(_toy_tracer())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 5
    assert all(e["ph"] == "X" for e in evs)
    assert all(set(e) >= {"name", "ts", "dur", "pid", "tid", "args"}
               for e in evs)
    # microseconds: the 1s-per-tick fake clock makes every dur >= 1e6
    assert all(e["dur"] >= 1e6 for e in evs)
    json.dumps(doc)


def test_summarize_phases_and_per_lambda():
    reg = MetricsRegistry()
    reg.counter("faults.kill").inc()
    s = summarize(_toy_tracer(), reg)
    assert s["spans"]["lambda_point"]["count"] == 2
    assert [r["name"] for r in s["roots"]] == ["path"]
    # phases = direct children of the root, grouped by name
    assert set(s["phases"]["path"]) == {"lambda_point"}
    assert len(s["per_lambda"]) == 2
    row = s["per_lambda"][0]
    assert row["index"] == 0 and row["nnz"] == 3
    assert set(row["phases"]) == {"restricted_solve"}
    assert s["counters"]["faults.kill"] == 1


def test_obs_session_export_and_report_cli(tmp_path, capsys):
    sess = ObsSession(_toy_tracer(), MetricsRegistry())
    files = sess.export(str(tmp_path / "run"))
    assert set(files) == {"trace", "events", "summary"}
    with open(files["trace"]) as fh:
        assert json.load(fh)["traceEvents"]
    with open(files["events"]) as fh:
        lines = [json.loads(ln) for ln in fh]
    assert len(lines) == 5 and all("sid" in r for r in lines)
    assert report_main([files["summary"]]) == 0
    out = capsys.readouterr().out
    assert "per-lambda phases" in out and "root span path" in out


def test_render_summary_serve_and_counter_lines():
    reg = MetricsRegistry()
    for v in (0.001, 0.002, 0.003):
        reg.histogram("serve.latency_s").observe(v)
    reg.counter("faults.swap").inc()
    reg.register_callback("residency.tile8",
                          lambda: {"hits": 3, "misses": 1, "evictions": 2,
                                   "bytes_h2d": 64})
    text = render_summary(summarize(None, reg))
    assert "serve submit->score latency (3 requests)" in text
    assert "residency.tile8: hit rate 0.75" in text
    assert "faults.swap=1" in text


# ---------------------------------------------------------------------------
# integration: adapters stay bit-identical; a traced real solve adds up
# ---------------------------------------------------------------------------

def _drive_batcher(batcher):
    from repro.serve import Overloaded

    for i in range(12):
        try:
            batcher.submit({f"tok{i}": 1.0}, 0.5)
        except Overloaded:
            pass
    batcher.drain()
    return dict(batcher.stats)


def test_batcher_stats_bit_identical_with_and_without_obs(monkeypatch):
    from repro.serve import RequestBatcher

    def build():
        return RequestBatcher(16, max_batch=8, max_pending=8)

    # untraced, nothing records a span or opens a profiler annotation
    recorded = []
    monkeypatch.setattr(Tracer, "_record",
                        lambda self, *a: recorded.append(a))
    monkeypatch.setattr(obs_trace, "_annotation",
                        lambda name: recorded.append(name))
    stats_off = _drive_batcher(build())
    assert recorded == []
    monkeypatch.undo()
    with observe() as obs:
        stats_on = _drive_batcher(build())
        mirrored = obs.registry.collect()["callbacks"]["serve.batcher"]
        collected = obs.registry.collect()
    assert stats_on == stats_off                 # legacy dict untouched
    assert mirrored == stats_on                  # registry mirrors it
    assert collected["callbacks"]["serve.queue"] == {"depth": 0}
    assert "serve.queue_depth" not in collected["gauges"]
    assert {r["name"] for r in obs.tracer.spans} == {"encode", "drain",
                                                     "pack"}


def test_traced_tiny_path_phases_add_up():
    from repro.api import DenseDesign, LogisticL1
    from repro.core.dglmnet import DGLMNETOptions

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(60, 24)), jnp.float32)
    y = jnp.asarray((rng.random(60) < 0.5).astype(np.float32))
    est = LogisticL1(opts=DGLMNETOptions(num_blocks=4, tile=8, max_iters=5))
    with observe() as obs:
        path = est.path(DenseDesign(X), y, path_len=3)
    s = obs.summary()
    root = s["roots"][0]
    assert root["name"] == "path" and root["args"]["path_len"] == 3
    assert root["args"]["points"] == len(path) == 3
    assert len(s["per_lambda"]) == 3
    for row in s["per_lambda"]:
        assert {"index", "lam", "nnz", "status", "dur_s"} <= set(row)
    # acceptance: direct-child phase totals account for the root wall
    # time to within 5% (gaps = strategy resolution, loop bookkeeping)
    covered = sum(s["phases"]["path"].values())
    assert covered <= root["dur_s"] * 1.0001
    assert covered >= root["dur_s"] * 0.95, (covered, root["dur_s"])
    # untraced rerun is bit-identical (tracing changed no math)
    path2 = est.path(DenseDesign(X), y, path_len=3)
    assert np.array_equal(np.asarray(path.betas), np.asarray(path2.betas))


# ---------------------------------------------------------------------------
# serve spans: one batch id from drain to fetch, bytes, waits, compiles,
# and the profiler's trace
# ---------------------------------------------------------------------------

def _serve_round(p):
    """A store and batcher at width ``p`` (a scoring shape no other test
    of this file compiles), and ``round_(reqs)``, which submits
    ``reqs``, drains once, scores the batch and returns it."""
    from repro.api.types import PathResult
    from repro.serve import PathScorer, PathStore, RequestBatcher

    path = PathResult(
        lambdas=np.asarray([1.0, 0.5]),
        betas=jnp.asarray(np.random.default_rng(p).normal(size=(2, p)),
                          jnp.float32),
        nnz=np.asarray([p, p]), f=np.zeros(2), n_iters=np.ones(2, np.int64),
        metrics=[{}, {}], screen=[{}, {}])
    batcher = RequestBatcher(p, max_batch=8)
    scorer = PathScorer(PathStore(path))

    def round_(reqs):
        for r in reqs:
            batcher.submit(r, 0.5)
        batch, lams = batcher.drain()
        scorer.score(batch, lams)
        return batch

    return round_


def _by_name(spans):
    out = {}
    for r in spans:
        out.setdefault(r["name"], []).append(r)
    return out


def test_serve_spans_share_one_batch_id():
    round_ = _serve_round(41)
    tr = Tracer()
    with obs_trace.use_tracer(tr):
        round_([{"a": 1.0}, {"b": 2.0, "c": 1.0}, {"d": 0.5}])
    by = _by_name(tr.spans)
    (drain,), (pack,), (score,) = by["drain"], by["pack"], by["score"]
    assert pack["parent"] == drain["sid"]
    kids = [r for r in tr.spans if r["parent"] == score["sid"]]
    assert [r["name"] for r in sorted(kids, key=lambda r: r["ts"])] == [
        "put", "launch", "fetch"]
    bid = drain["args"]["batch"]
    assert all(r["args"]["batch"] == bid for r in [pack, score] + kids)
    lo, hi = drain["args"]["req"]
    assert drain["args"]["drained"] == 3 == hi - lo + 1
    assert sorted(r["args"]["req"] for r in by["encode"]) == [lo, lo + 1,
                                                              hi]


def test_put_bytes_are_the_arrays_placed():
    round_ = _serve_round(43)
    tr = Tracer()
    with obs_trace.use_tracer(tr):
        batch = round_([{"a": 1.0}, {"b": 2.0}])
    (put,) = _by_name(tr.spans)["put"]
    lam_idx_nbytes = batch.batch_cap * np.dtype(np.int32).itemsize
    assert put["args"]["bytes"] == (
        batch.entry_row.nbytes + batch.entry_feat.nbytes
        + batch.entry_val.nbytes + lam_idx_nbytes)


def test_put_counts_entries_slots_and_classes_over_the_floor():
    from repro.serve.ingest import ENTRY_FLOOR_PER_ROW

    p = 1 << 16
    round_ = _serve_round(p)
    floor = ENTRY_FLOOR_PER_ROW * 8            # batch capacity 8
    big = {f"w{i}": 1.0 for i in range(floor + 400)}
    reg, tr = MetricsRegistry(), Tracer()
    with obs_registry.use_registry(reg), obs_trace.use_tracer(tr):
        small = round_([{"a": 1.0, "b": 2.0}, {"c": 0.5}])
        assert reg.value("serve.entry_class_over_floor") is None
        large = round_([big])
    put_small, put_large = _by_name(tr.spans)["put"]
    assert put_small["args"]["entries"] == small.n_entries == 3
    assert put_small["args"]["slots"] == floor
    assert floor < large.n_entries <= len(big)
    assert put_large["args"]["entries"] == large.n_entries
    assert put_large["args"]["slots"] == 2 * floor
    assert reg.value("serve.entry_class_over_floor") == 1


def test_drain_wait_is_the_summed_queue_wait():
    now = [0.0]
    tr = Tracer()
    with obs_trace.use_tracer(tr):
        from repro.serve import RequestBatcher

        batcher = RequestBatcher(16, max_batch=8, clock=lambda: now[0])
        for t in (1.0, 2.0, 3.5):
            now[0] = t
            batcher.submit({f"t{t}": 1.0}, 0.5)
        now[0] = 10.0
        batcher.drain()
        batcher.drain()                          # empty: waits nothing
    full, empty = _by_name(tr.spans)["drain"]
    assert full["args"]["wait_s"] == (10.0 - 1.0) + (10.0 - 2.0) + (
        10.0 - 3.5)
    assert empty["args"]["drained"] == 0 and empty["args"]["wait_s"] == 0.0
    assert "req" not in empty["args"]


def test_first_score_of_a_shape_records_its_compile_under_launch():
    round_ = _serve_round(47)
    tr = Tracer()
    with obs_trace.use_tracer(tr):
        round_([{"a": 1.0}])
    by = _by_name(tr.spans)
    (launch,) = by["launch"]
    assert by["compile"], "the new scoring shape compiled no program"
    for c in by["compile"]:
        assert c["parent"] == launch["sid"] and c["dur"] > 0
        assert launch["ts"] <= c["ts"] and (
            c["ts"] + c["dur"] <= launch["ts"] + launch["dur"])
    tr2 = Tracer()
    with obs_trace.use_tracer(tr2):
        round_([{"b": 1.0}])
    names = {r["name"] for r in tr2.spans}
    assert "launch" in names and "compile" not in names


def test_spans_land_in_the_profiler_trace_on_its_clock(tmp_path):
    import time

    import jax
    from jax.profiler import ProfileData

    anchor = "obs_test_anchor"
    round_ = _serve_round(51)
    tr = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(anchor):
            anchor_perf = time.perf_counter()
        with obs_trace.use_tracer(tr):
            round_([{"a": 1.0}, {"b": 2.0}])
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(xplane))
    host = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                host.setdefault(ev.name, []).append(int(ev.start_ns))
    (a_ns,) = host[anchor]
    by = _by_name(tr.spans)
    assert {"encode", "drain", "pack", "score", "put", "launch",
            "fetch"} <= set(by)
    for name, recs in by.items():
        if name == "compile":                    # recorded after the fact
            continue
        starts = sorted(host.get(name, []))
        assert len(starts) == len(recs), name
        for rec, ns in zip(sorted(recs, key=lambda r: r["ts"]), starts):
            perf = tr.t0 + rec["ts"]
            want = a_ns + (perf - anchor_perf) * 1e9
            assert abs(ns - want) < 1e6, (name, ns - want)
