"""A run whose timed path is broken underneath comes out not correct.

Each test drives a tiny cell through the harness on the CPU (the chip
refusal lives in ``bench/run.py`` alone) with one fault planted in the
program, and sees ``correct`` false: a solve that returns its state
unchanged, half of the examples left out, an answer altered where it is
produced; for serving also half of a batch left out, a token altered at
ingest and a scorer that returns its last batch's scores. The cells run
on one chip, so no exchange between chips can be left out.
"""
import dataclasses
import json
import time

import numpy as np
import pytest

from bench.harness import ROOT, load_cell, run_cell
from bench.tests.cells import write_cells


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return write_cells(tmp_path_factory.mktemp("checkout"), spec)


def _run(root, name):
    return run_cell(load_cell(name, root), seed=31, seconds=1.5,
                    trace=False, t_start=time.perf_counter())


@pytest.mark.parametrize("name", ["tinydense.path"])
def test_sound_fit_is_correct(root, name):
    assert _run(root, name)["correct"]


@pytest.mark.parametrize("name", ["tinydense.path"])
def test_solve_returning_its_state_unchanged(root, name, monkeypatch):
    from repro.api import estimator

    real = estimator._solve

    def unchanged(design, y, lam, strat, *, beta0=None, **kw):
        res = real(design, y, lam, strat, beta0=beta0, **kw)
        return dataclasses.replace(
            res, beta=res.beta * 0 if beta0 is None else beta0)

    monkeypatch.setattr(estimator, "_solve", unchanged)
    assert not _run(root, name)["correct"]


@pytest.mark.parametrize("name", ["tinydense.path"])
def test_answer_altered_where_produced(root, name, monkeypatch):
    import jax.numpy as jnp

    from repro.api import LogisticL1

    real = LogisticL1.path

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        b = jnp.asarray(res.betas)
        top = jnp.argmax(jnp.abs(b), axis=1)
        res.betas = b.at[jnp.arange(b.shape[0]), top].set(0.0)
        return res

    monkeypatch.setattr(LogisticL1, "path", altered)
    assert not _run(root, name)["correct"]


def test_half_of_the_examples_left_out(root, monkeypatch):
    from repro.api import DenseDesign, LogisticL1

    real = LogisticL1.path

    def half(self, data, y, **kw):
        n = y.shape[0] // 2
        return real(self, DenseDesign(data.X[:n]), y[:n], **kw)

    monkeypatch.setattr(LogisticL1, "path", half)
    assert not _run(root, "tinydense.path")["correct"]


def test_sound_serving_is_correct(root):
    assert _run(root, "tinywide.docs")["correct"]


def _patch_score(monkeypatch, wrap):
    from repro.serve import PathScorer

    real = PathScorer.score
    monkeypatch.setattr(PathScorer, "score",
                        lambda self, b, lams: wrap(real(self, b, lams), b))


def test_half_of_a_batch_left_out(root, monkeypatch):
    _patch_score(monkeypatch, lambda out, b: (out[0][: b.n_live // 2],
                                              out[1]))
    assert not _run(root, "tinywide.docs")["correct"]


def test_served_answer_altered(root, monkeypatch):
    _patch_score(monkeypatch, lambda out, b: (out[0] * (1 + 1e-3), out[1]))
    assert not _run(root, "tinywide.docs")["correct"]


def test_scorer_returning_its_last_scores(root, monkeypatch):
    last = {}

    def stale(out, b):
        prev = last.get("s", np.zeros(0, np.float32))
        last["s"] = out[0]
        s = np.resize(prev, out[0].shape) if prev.size else out[0] * 0
        return s, out[1]

    _patch_score(monkeypatch, stale)
    assert not _run(root, "tinywide.docs")["correct"]


def test_token_altered_at_ingest(root, monkeypatch):
    from repro.serve import batcher

    real = batcher.encode_request

    def altered(request, p):
        idx, val = real(request, p)
        return idx, np.where(np.arange(val.size) == 0, 2 * val, val)

    monkeypatch.setattr(batcher, "encode_request", altered)
    assert not _run(root, "tinywide.docs")["correct"]
