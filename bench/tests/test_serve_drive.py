"""The open-loop generator times each request from its due time, so a
scorer that stalls shows in the latency of the requests behind it."""
import json
import time

import numpy as np
import pytest

from bench.harness import ROOT, load_cell, run_cell
from bench.tests.cells import write_cells


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    root = write_cells(tmp_path_factory.mktemp("checkout"), spec)
    return load_cell("tinywide.docs", root)


def test_stalled_scorer_shows_in_later_latency(cell):
    serve = cell.module("drive", "serve")
    state = serve.setup(cell, 5, 3.0, lambda m: None)
    stall_s, calls = 1.0, [0]
    real = state.scorer.score

    def stalling(batch, lams):
        calls[0] += 1
        if calls[0] == 2:
            time.sleep(stall_s)
        return real(batch, lams)

    state.scorer.score = stalling
    out = serve.run_window(state, 3.0, 20.0)
    lat = out["done"] - out["due"]
    assert np.all(np.isfinite(lat))
    assert lat.max() >= stall_s
    # requests due while the stall ran waited for it, from their due time
    stalled_at = np.nanmin(out["done"][out["done"] > out["due"][0]])
    behind = out["due"] > stalled_at - stall_s
    assert np.median(lat[behind][:3]) > 0.3 * stall_s
    win = serve.summarize(state, out, 3.0)
    assert win.metrics["serve_p95_ms"] >= 1e3 * np.sort(lat)[
        int(np.ceil(0.95 * lat.size)) - 1] - 1e-6


def test_unscored_requests_are_misses_at_the_top(cell):
    serve = cell.module("drive", "serve")
    state = serve.setup(cell, 6, 2.0, lambda m: None)

    def lose_half(batch, lams):
        raise RuntimeError("scorer lost")

    state.scorer.score = lose_half
    out = serve.run_window(state, 2.0, 1.0)
    win = serve.summarize(state, out, 2.0)
    assert win.failed == win.attempted
    assert win.metrics["serve_p95_ms"] == float("inf")


def test_lateness_is_printed_before_the_checks(cell, capsys):
    run_cell(cell, seed=7, seconds=2.0, trace=False,
             t_start=time.perf_counter())
    err = capsys.readouterr().err.splitlines()
    late = [i for i, l in enumerate(err) if "generator lateness" in l]
    checks = [i for i, l in enumerate(err) if l.startswith("check ")]
    assert late and checks and late[0] < checks[0]
    assert err[-1].startswith("check ")
