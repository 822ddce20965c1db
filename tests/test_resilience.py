"""PR 8 robustness layer: fault injection, typed status, degradation,
resume, bounded serving.

Fast tests run the full stack on a small local problem; the mesh drill
(`chaos_glm --smoke --mesh 2x4`) is a slow subprocess test, mirroring
tests/test_distributed.py's isolation rule (this process sees 1 device).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import LogisticL1, PathResult
from repro.checkpoint import CheckpointCorruption
from repro.configs.base import GLMConfig
from repro.core import engine
from repro.data.synthetic import make_glm_dataset
from repro.resilience import (
    EngineFault,
    FaultPlan,
    InjectedFault,
    InjectedKill,
    PathProgress,
    RetriesExhausted,
    active_plan,
    corrupt_checkpoint,
    inject_faults,
    retry_call,
)
from repro.serve import (
    InvalidRequest,
    NonFiniteScores,
    Overloaded,
    PathScorer,
    PathStore,
    RequestBatcher,
    batch_capacity,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAM = 0.05


@pytest.fixture(scope="module")
def tiny_glm():
    cfg = GLMConfig(name="resilience", num_examples=256, num_features=64,
                    density=0.1)
    ds = make_glm_dataset(cfg, jax.random.key(0))
    return ds.X_train, ds.y_train


# ---------------------------------------------------------------------------
# fault plan plumbing
# ---------------------------------------------------------------------------

def test_engine_fault_validation():
    with pytest.raises(ValueError):
        EngineFault("margins", at_iter=0)
    with pytest.raises(ValueError):
        EngineFault("gradients", at_iter=1)
    with pytest.raises(ValueError):
        EngineFault("margins", at_iter=1, mode="zero")


def test_inject_faults_rejects_nesting():
    with inject_faults(FaultPlan()):
        with pytest.raises(RuntimeError):
            with inject_faults(FaultPlan()):
                pass
    assert active_plan() is None


def test_retry_call_backoff_and_exhaustion():
    calls, delays = [], []
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"
    assert retry_call(flaky, attempts=3, sleep=delays.append) == "ok"
    assert len(calls) == 3 and len(delays) == 2
    assert delays[1] == 2 * delays[0]        # exponential

    def always():
        raise RuntimeError("permanent")
    with pytest.raises(RetriesExhausted) as ei:
        retry_call(always, attempts=2, sleep=lambda s: None)
    assert isinstance(ei.value.__cause__, RuntimeError)
    with pytest.raises(ValueError):           # not in retry_on: no retry
        retry_call(lambda: (_ for _ in ()).throw(ValueError("x")),
                   attempts=3, sleep=lambda s: None)


# ---------------------------------------------------------------------------
# engine guardrails (tentpole b)
# ---------------------------------------------------------------------------

def test_nan_margins_trips_typed_status(tiny_glm):
    X, y = tiny_glm
    est = LogisticL1()
    base = est.fit(X, y, LAM)
    assert base.ok and base.status_name == "OK" and base.status == 0

    plan = FaultPlan(engine=EngineFault("margins", at_iter=3),
                     engine_fires=1)
    with inject_faults(plan):
        res = est.fit(X, y, LAM)
    assert res.status == engine.STATUS_NONFINITE_OBJECTIVE
    assert res.status_name == "NONFINITE_OBJECTIVE" and not res.ok
    # last certified iterate: 2 real iterations, all-finite history that
    # is an exact prefix of the healthy trajectory
    assert res.n_iters == 2
    assert np.all(np.isfinite(np.asarray(res.beta)))
    assert all(np.isfinite(res.objective_history))
    k = len(res.objective_history)
    assert res.objective_history == base.objective_history[:k]

    # the healthy compiled-solver cache was never poisoned
    again = est.fit(X, y, LAM)
    assert again.ok
    assert np.array_equal(np.asarray(again.beta), np.asarray(base.beta))
    assert again.objective_history == base.objective_history


def test_stats_poison_at_first_iter_returns_warm_start(tiny_glm):
    X, y = tiny_glm
    plan = FaultPlan(engine=EngineFault("stats", at_iter=1, mode="inf"),
                     engine_fires=1)
    with inject_faults(plan):
        res = LogisticL1().fit(X, y, LAM)
    assert res.status == engine.STATUS_NONFINITE_OBJECTIVE
    assert res.n_iters == 0
    assert np.array_equal(np.asarray(res.beta),
                          np.zeros_like(np.asarray(res.beta)))


def test_forced_linesearch_stall_trips(tiny_glm):
    X, y = tiny_glm
    plan = FaultPlan(engine=EngineFault("linesearch", at_iter=2),
                     engine_fires=1)
    with inject_faults(plan):
        res = LogisticL1().fit(X, y, LAM)
    assert res.status == engine.STATUS_LINESEARCH_STALLED
    assert res.status_name == "LINESEARCH_STALLED"
    assert res.n_iters == 1
    assert np.all(np.isfinite(np.asarray(res.beta)))


def test_fetch_rejects_ok_status_with_poisoned_history():
    z = np.zeros(2, np.float32)
    mk = lambda status: engine.SolverState(
        beta=z, m=z, f=np.float32(1.0), it=np.int32(1), done=np.bool_(True),
        converged=np.bool_(True), dbeta=z, dm=z, alpha=np.float32(1.0),
        f_new=np.float32(1.0),
        f_hist=np.array([1.0, np.nan, 0.0], np.float32),
        a_hist=np.array([1.0, 0.0], np.float32),
        unit_steps=np.int32(1), status=np.int32(status))
    with pytest.raises(RuntimeError, match="invariant"):
        engine.fetch(mk(engine.STATUS_OK))
    # a tripped solve trims the poisoned tail instead of raising
    host, f_hist, a_hist = engine.fetch(
        mk(engine.STATUS_NONFINITE_OBJECTIVE))
    assert f_hist == [1.0] and a_hist == []


# ---------------------------------------------------------------------------
# path degradation ladder + resume (tentpole b/c)
# ---------------------------------------------------------------------------

def test_path_recovers_transient_fault_bit_identically(tiny_glm):
    X, y = tiny_glm
    est = LogisticL1()
    healthy = est.path(X, y, path_len=3)
    assert healthy.all_ok

    plan = FaultPlan(engine=EngineFault("margins", at_iter=1),
                     engine_fires=1)
    with inject_faults(plan):
        recovered = est.path(X, y, path_len=3)
    # the one poisoned solve was retried down the ladder; the certified
    # output is bit-identical to the healthy run
    assert recovered.all_ok
    assert np.array_equal(np.asarray(recovered.betas),
                          np.asarray(healthy.betas))
    assert any("degraded" in s for s in recovered.screen)


def test_path_persistent_fault_skips_and_marks(tiny_glm):
    X, y = tiny_glm
    plan = FaultPlan(engine=EngineFault("margins", at_iter=1),
                     engine_fires=10 ** 9)
    with inject_faults(plan):
        res = LogisticL1().path(X, y, path_len=3)
    assert not res.all_ok
    assert np.all(res.statuses == engine.STATUS_NONFINITE_OBJECTIVE)
    assert all(s.get("skipped") and s.get("degraded") == "skipped"
               for s in res.screen)
    assert np.all(np.isfinite(np.asarray(res.betas)))
    assert np.all(res.n_iters == 0)


def test_killed_path_resumes_bit_identically(tiny_glm, tmp_path):
    X, y = tiny_glm
    est = LogisticL1()
    full = est.path(X, y, path_len=3)

    d = str(tmp_path / "progress")
    with pytest.raises(InjectedKill):
        with inject_faults(FaultPlan(kill_after_points=2)):
            est.path(X, y, path_len=3, checkpoint_every=1, resume_from=d)
    resumed = est.path(X, y, path_len=3, checkpoint_every=1, resume_from=d)
    assert np.array_equal(np.asarray(resumed.betas), np.asarray(full.betas))
    assert np.array_equal(resumed.lambdas, full.lambdas)
    assert np.array_equal(resumed.f, full.f)
    assert np.array_equal(resumed.nnz, full.nnz)
    assert np.array_equal(resumed.statuses, full.statuses)


def test_path_resume_validates_grid(tiny_glm, tmp_path):
    X, y = tiny_glm
    est = LogisticL1()
    d = str(tmp_path / "progress")
    with pytest.raises(InjectedKill):
        with inject_faults(FaultPlan(kill_after_points=1)):
            est.path(X, y, path_len=3, checkpoint_every=1, resume_from=d)
    with pytest.raises(ValueError, match="different path"):
        est.path(X, y, path_len=4, checkpoint_every=1, resume_from=d)
    with pytest.raises(ValueError, match="requires resume_from"):
        est.path(X, y, path_len=3, checkpoint_every=1)


def test_progress_rolls_back_over_corrupted_slot(tmp_path):
    prog = PathProgress(str(tmp_path), keep=2)
    for i in range(2):
        prog.save(i, {"beta": jnp.arange(3, dtype=jnp.float32) + i},
                  {"kind": "PathProgress", "next_index": i + 1})
    assert prog.pointer() == 1
    corrupt_checkpoint(prog.slot(1), "bitflip")
    idx, arrays, meta = prog.load_latest()
    assert idx == 0 and meta["next_index"] == 1
    assert np.array_equal(arrays["beta"], np.arange(3, dtype=np.float32))


# ---------------------------------------------------------------------------
# bounded serve loop (tentpole d + satellite 1)
# ---------------------------------------------------------------------------

def _path_result(p=16, seed=0):
    rng = np.random.default_rng(seed)
    return PathResult(
        lambdas=np.asarray([1.0, 0.5]),
        betas=jnp.asarray(rng.normal(size=(2, p)), jnp.float32),
        nnz=np.asarray([3, 5]), f=np.asarray([1.0, 0.9]),
        n_iters=np.asarray([2, 3]))


def test_batch_capacity_rejects_non_pow2():
    assert batch_capacity(5) == 8 and batch_capacity(65) == 128
    with pytest.raises(ValueError, match="power of two"):
        batch_capacity(5, b_min=10)
    with pytest.raises(ValueError, match="power of two"):
        batch_capacity(5, b_max=100)
    with pytest.raises(ValueError, match="exceeds"):
        batch_capacity(5, b_min=64, b_max=32)
    with pytest.raises(ValueError, match="power of two"):
        RequestBatcher(16, max_batch=100)


def test_batcher_bounded_queue_and_deadlines():
    t = [0.0]
    b = RequestBatcher(16, max_batch=8, max_pending=3,
                       default_ttl_s=10.0, clock=lambda: t[0])
    b.submit({"a": 1.0}, 0.1, deadline_s=1.0)
    b.submit({"b": 2.0}, 0.1)                    # default ttl 10s
    b.submit({"c": 3.0}, 0.1, deadline_s=5.0)
    with pytest.raises(Overloaded):
        b.submit({"d": 4.0}, 0.1)
    with pytest.raises(InvalidRequest):          # rejected, not queued
        b.submit({"e": float("nan")}, 0.1)
    assert len(b) == 3
    t[0] = 2.0                                    # "a" expires
    batch, lams = b.drain()
    assert batch.n_live == 2 and len(lams) == 2
    assert b.stats == {"submitted": 3, "rejected_overload": 1,
                       "rejected_invalid": 1, "shed_expired": 1,
                       "drained": 2}
    # empty queue drains to an all-padding batch
    batch, lams = b.drain()
    assert batch.n_live == 0 and lams.size == 0


def test_swap_retries_injected_failures():
    with inject_faults(FaultPlan(fail_swaps=2)):
        store = PathStore(_path_result())       # attempts 1+2 fail, 3 lands
    assert store.version == 1
    with inject_faults(FaultPlan(fail_swaps=3)):
        with pytest.raises(RetriesExhausted) as ei:
            store.swap(_path_result(), attempts=2)
    assert isinstance(ei.value.__cause__, InjectedFault)
    assert store.version == 1                   # still serving last-good
    assert store.snapshot.version == 1


def test_nonfinite_scores_pin_store_to_last_good():
    p = 16
    good = _path_result(p)
    bad_b = np.full((2, p), np.nan, np.float32)
    bad = PathResult(lambdas=good.lambdas, betas=jnp.asarray(bad_b),
                     nnz=good.nnz, f=good.f, n_iters=good.n_iters)
    store = PathStore(good)
    scorer = PathScorer(store)
    b = RequestBatcher(p, max_batch=8)
    b.submit({"tok3": 1.5}, 0.5)
    batch, lams = b.drain()
    ref, v1 = scorer.score(batch, lams)
    assert v1 == 1 and np.all(np.isfinite(ref))

    store.swap(bad)
    assert store.snapshot.version == 2
    scores, ver = scorer.score(batch, lams)     # quarantines v2, rescores
    assert ver == 1 and np.array_equal(scores, ref)
    assert store.quarantined == [2]
    assert store.snapshot.version == 1

    # no last-good to fall back to -> typed error, never NaN out
    with pytest.raises(NonFiniteScores):
        PathScorer(PathStore(bad)).score(batch, lams)


def test_from_checkpoint_retries_and_surfaces_corruption(tmp_path):
    d = str(tmp_path / "path")
    good = _path_result()
    good.save(d)
    with inject_faults(FaultPlan(fail_loads=1)):
        store = PathStore.from_checkpoint(d)
    assert store.version == 1
    corrupt_checkpoint(d, "bitflip")
    with pytest.raises(RetriesExhausted) as ei:
        PathStore.from_checkpoint(d, attempts=2)
    assert isinstance(ei.value.__cause__, CheckpointCorruption)


def test_serve_latency_injection_is_scoped():
    import time

    store = PathStore(_path_result())
    scorer = PathScorer(store)
    b = RequestBatcher(16, max_batch=8)
    b.submit({"x": 1.0}, 1.0)
    batch, lams = b.drain()
    scorer.score(batch, lams)                    # warm the program
    with inject_faults(FaultPlan(serve_latency_s=0.05)):
        t0 = time.perf_counter()
        scorer.score(batch, lams)
        # allow[bench-timing]: times an injected host-side sleep floor; score() materializes to numpy before returning, so the section is host-synchronous
        slowed = time.perf_counter() - t0
    assert slowed >= 0.05                        # injected floor applies


# ---------------------------------------------------------------------------
# the chaos drill end-to-end on a 2x4 mesh (the CI chaos-smoke lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_smoke_on_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # the launcher turns the persistent compile cache on unless told not to
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.chaos_glm", "--smoke",
         "--mesh", "2x4"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "CHAOS SMOKE OK" in r.stdout
