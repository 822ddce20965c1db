"""Chaos drills for the solver/serve stack: seeded fault injection.

    PYTHONPATH=src python -m repro.launch.chaos_glm --smoke
    PYTHONPATH=src python -m repro.launch.chaos_glm --smoke --mesh 2x4
    PYTHONPATH=src python -m repro.launch.chaos_glm --scenario kill-resume

Each scenario arms a deterministic :class:`repro.resilience.FaultPlan`
and asserts the stack's contracted reaction — these are the same checks
as ``tests/test_resilience.py``, runnable standalone against any mesh
geometry:

* ``nan-inject``  — NaN poisons the margins at outer iteration k; the
  engine must trip ``NONFINITE_OBJECTIVE``, return the last finite
  iterate (history an exact prefix of the healthy run), and the healthy
  solver cache must stay bit-identical afterwards.
* ``kill-resume`` — the path driver is killed after N points (checkpoint
  already landed); resuming from the progress directory must reproduce
  the uninterrupted path bit-for-bit.
* ``corrupt``     — bit-flipped / truncated checkpoints must surface as
  typed ``CheckpointCorruption`` (never silently load), and the rotated
  progress store must roll back to the last-good slot.
* ``overload``    — the bounded serve loop under latency + swap faults:
  admission control rejects, deadlines shed at drain, poisoned
  coefficients quarantine back to the last-good snapshot, and every
  casualty shows up in the telemetry counters.
* ``lost-bucket`` — the streamed bucket-residency manager under prefetch
  failure: a transient lost bucket is absorbed by retry (path stays
  bit-identical to the resident solve); a fatal failure window placed
  mid-path kills the streamed solve after a checkpoint, and resuming via
  ``PathProgress`` reproduces the path bit-for-bit.

``--trace PATH`` runs the scenarios under ``repro.obs.observe()`` and, on
top of the legacy assertions above, asserts each scenario's injected
faults showed up in the ``faults.*`` / ``retry.*`` registry counters —
then exports ``PATH.trace.json`` / ``PATH.summary.json`` and checks the
counters survived into the dump.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import tempfile

if "--mesh" in sys.argv:
    # fake-device flag must land before the first jax import (same dance
    # as launch.serve_glm); fail loudly on an unraisable count
    try:
        _spec = sys.argv[sys.argv.index("--mesh") + 1]
    except IndexError:
        _spec = ""
    _need = 1
    for _d in re.findall(r"\d+", _spec):
        _need *= int(_d)
    if _need > 1:
        _flags = os.environ.get("XLA_FLAGS", "")
        _m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                       _flags)
        if _m is None:
            os.environ["XLA_FLAGS"] = (
                _flags + f" --xla_force_host_platform_device_count={_need}"
            )
        elif int(_m.group(1)) < _need:
            sys.exit(
                f"--mesh {_spec} needs >= {_need} fake devices but "
                f"XLA_FLAGS already forces {_m.group(1)}"
            )

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import LogisticL1, PathResult
from repro.checkpoint import CheckpointCorruption, verify_payload
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import observe
from repro.configs.base import GLMConfig
from repro.core import engine
from repro.data.synthetic import make_glm_dataset
from repro.resilience import (
    EngineFault,
    FaultPlan,
    InjectedKill,
    PathProgress,
    RetriesExhausted,
    corrupt_checkpoint,
    inject_faults,
)
from repro.serve import (
    InvalidRequest,
    NonFiniteScores,
    Overloaded,
    PathScorer,
    PathStore,
    RequestBatcher,
)

_SCENARIOS = ("nan-inject", "kill-resume", "corrupt", "overload",
              "lost-bucket")

#: fault counters (see repro.resilience / repro.obs) each scenario MUST
#: bump when it runs under --trace; asserted against the live registry
#: and again against the exported summary dump
_EXPECT = {
    "nan-inject": ("faults.engine",),
    "kill-resume": ("faults.kill",),
    "corrupt": ("retry.retries",),
    "overload": ("faults.swap", "faults.serve_delay"),
    "lost-bucket": ("faults.prefetch", "retry.retries"),
}


def _dataset(args, mesh):
    cfg = GLMConfig(name="chaos-glm", num_examples=args.n,
                    num_features=args.p, density=0.1)
    ds = make_glm_dataset(cfg, jax.random.key(0))
    X, y = ds.X_train, ds.y_train
    if mesh is not None:
        from repro.core.distributed import _data_extent

        n_trim = (X.shape[0] // _data_extent(mesh)) * _data_extent(mesh)
        X, y = X[:n_trim], y[:n_trim]
    return X, y


def scenario_nan_inject(args, mesh) -> None:
    """NaN at iteration k trips the typed status; cache stays healthy."""
    X, y = _dataset(args, mesh)
    est = LogisticL1(mesh=mesh) if mesh is not None else LogisticL1()
    lam = 0.05
    base = est.fit(X, y, lam)
    assert base.ok and base.status_name == "OK"

    plan = FaultPlan(engine=EngineFault("margins", at_iter=3), engine_fires=1)
    with inject_faults(plan):
        res = est.fit(X, y, lam)
    assert res.status == engine.STATUS_NONFINITE_OBJECTIVE, res.status
    assert res.status_name == "NONFINITE_OBJECTIVE"
    assert res.n_iters == 2, res.n_iters    # last certified iterate
    assert np.all(np.isfinite(np.asarray(res.beta)))
    nb = len(res.objective_history)
    assert res.objective_history == base.objective_history[:nb]

    again = est.fit(X, y, lam)              # healthy cache untouched
    assert again.ok
    assert np.array_equal(np.asarray(again.beta), np.asarray(base.beta))
    print(f"# nan-inject: status={res.status_name} after iter "
          f"{res.n_iters}, beta finite, healthy solve bit-identical")


def scenario_kill_resume(args, mesh) -> None:
    """Mid-path kill + resume reproduces the path bit-for-bit."""
    X, y = _dataset(args, mesh)
    est = LogisticL1(mesh=mesh) if mesh is not None else LogisticL1()
    kw = dict(path_len=args.path_len, screen=True)
    full = est.path(X, y, **kw)

    with tempfile.TemporaryDirectory() as d:
        killed = False
        try:
            with inject_faults(FaultPlan(kill_after_points=2)):
                est.path(X, y, checkpoint_every=1, resume_from=d, **kw)
        except InjectedKill:
            killed = True
        assert killed, "kill_after_points never fired"
        resumed = est.path(X, y, checkpoint_every=1, resume_from=d, **kw)
    assert len(resumed) == len(full)
    assert np.array_equal(np.asarray(resumed.betas), np.asarray(full.betas))
    assert np.array_equal(resumed.lambdas, full.lambdas)
    assert np.array_equal(resumed.f, full.f)
    assert np.array_equal(resumed.nnz, full.nnz)
    print(f"# kill-resume: killed after 2/{len(full)} points, resume "
          f"bit-identical across all {len(full)} points")


def scenario_corrupt(args, mesh) -> None:
    """Corrupted checkpoints surface typed errors; progress rolls back."""
    X, y = _dataset(args, mesh)
    est = LogisticL1(mesh=mesh) if mesh is not None else LogisticL1()
    path = est.path(X, y, path_len=args.path_len)

    for mode in ("bitflip", "truncate", "drop-meta"):
        with tempfile.TemporaryDirectory() as d:
            path.save(d)
            assert verify_payload(d) is True
            corrupt_checkpoint(d, mode)
            try:
                PathStore.from_checkpoint(d, mesh=mesh, attempts=2)
            except (CheckpointCorruption, RetriesExhausted, ValueError):
                pass
            else:
                raise SystemExit(f"FAIL: {mode} corruption loaded silently")

    with tempfile.TemporaryDirectory() as d:
        prog = PathProgress(d, keep=2)
        for i in range(2):
            prog.save(i, {"beta": jnp.arange(4, dtype=jnp.float32) + i},
                      {"kind": "PathProgress", "next_index": i + 1})
        corrupt_checkpoint(prog.slot(1), "bitflip")
        idx, arrays, meta = prog.load_latest()
        assert idx == 0, idx                # rolled back to last-good slot
        assert np.array_equal(arrays["beta"], np.arange(4, dtype=np.float32))
    print("# corrupt: bitflip/truncate/drop-meta all detected; progress "
          "rolled back to last-good slot")


def scenario_overload(args, mesh) -> None:
    """Bounded serve loop under latency, overload and poisoned swaps."""
    X, y = _dataset(args, mesh)
    est = LogisticL1(mesh=mesh) if mesh is not None else LogisticL1()
    path = est.path(X, y, path_len=args.path_len)

    with inject_faults(FaultPlan(fail_swaps=1, serve_latency_s=0.005)):
        store = PathStore(path, mesh=mesh)   # survives the injected failure
        scorer = PathScorer(store)
        dp = 1
        if mesh is not None:
            from repro.core.distributed import _data_extent

            dp = _data_extent(mesh)
        t = [0.0]
        batcher = RequestBatcher(store.snapshot.p, max_batch=32, dp=dp,
                                 pad_p_to=store.pad_p_to, max_pending=8,
                                 default_ttl_s=1.0, clock=lambda: t[0])
        rng = np.random.default_rng(0)
        rejected = 0
        for i in range(12):                  # 8 admitted, 4 rejected
            req = {f"tok{int(v)}": float(rng.normal())
                   for v in rng.integers(0, 4 * store.snapshot.p, size=6)}
            try:
                batcher.submit(req, float(path.lambdas[0]))
            except Overloaded:
                rejected += 1
        try:
            batcher.submit({"x": float("inf")}, 1.0)
        except InvalidRequest:
            pass
        t[0] = 2.0                           # everything queued expires
        batch, lams = batcher.drain()
        assert batch.n_live == 0
        for i in range(4):                   # fresh, in-deadline traffic
            batcher.submit({f"tok{i}": 1.0}, float(path.lambdas[-1]))
        batch, lams = batcher.drain()
        scores, ver = scorer.score(batch, lams)
        assert np.all(np.isfinite(scores)) and len(scores) == 4

        # poisoned hot-swap: quarantine pins back to the good version
        bad_b = np.asarray(path.betas).copy()
        bad_b[:] = np.nan
        bad = PathResult(lambdas=path.lambdas, betas=jnp.asarray(bad_b),
                         nnz=path.nnz, f=path.f, n_iters=path.n_iters)
        store.swap(bad)
        scores2, ver2 = scorer.score(batch, lams)
        assert ver2 == ver and np.array_equal(scores2, scores)
        assert store.quarantined, "poisoned version was not quarantined"

        bad_only = PathStore(bad, mesh=mesh)
        try:
            PathScorer(bad_only).score(batch, lams)
        except NonFiniteScores:
            pass
        else:
            raise SystemExit("FAIL: poisoned-only store served NaN scores")

    stats = batcher.stats
    assert stats["rejected_overload"] == rejected == 4, stats
    assert stats["rejected_invalid"] == 1, stats
    assert stats["shed_expired"] == 8, stats
    assert stats["drained"] == 4, stats
    print(f"# overload: served {len(scores)} scores at v{ver} under "
          f"latency+swap faults; quarantined={store.quarantined}; "
          f"telemetry={stats}")


def _mixed_density_dataset(args, mesh, seed: int = 0):
    """Synthetic X with stratified per-column nnz so ``to_slab_buckets``
    yields several capacity classes — streamed residency needs >= 3
    buckets before the LRU can evict anything under a double buffer."""
    from repro.core.distributed import _data_extent

    rng = np.random.default_rng(seed)
    n, p = args.n, args.p
    n -= n % _data_extent(mesh)
    levels = [4, 12, 28, min(60, n // 2)]
    X = np.zeros((n, p), np.float32)
    for j in range(p):
        rows = rng.choice(n, size=levels[j % len(levels)], replace=False)
        X[rows, j] = rng.normal(size=rows.size).astype(np.float32)
    w = rng.normal(size=p) * (rng.random(p) < 0.3)
    prob = 1.0 / (1.0 + np.exp(-(X @ w)))
    y = np.where(rng.random(n) < prob, 1.0, -1.0).astype(np.float32)
    return X, y


def scenario_lost_bucket(args, mesh) -> None:
    """Streamed bucket residency under prefetch failure: transient faults
    are absorbed by retry (bit-identical to resident); a fatal failure
    window mid-path kills the solve after a checkpoint and the resume
    reproduces the path bit-for-bit."""
    from dataclasses import replace

    from repro.api import as_design
    from repro.core.distributed import _data_extent
    from repro.core.dglmnet import DGLMNETOptions
    from repro.data.byfeature import to_by_feature, to_slab_buckets
    from repro.launch.mesh import make_dev_mesh

    work_mesh = mesh if mesh is not None else make_dev_mesh(1, 1)
    X, y = _mixed_density_dataset(args, work_mesh)
    slabs = to_slab_buckets(to_by_feature(X), _data_extent(work_mesh))
    assert len(slabs.buckets) >= 3, \
        f"need >= 3 capacity classes to stream, got {slabs.k_classes}"

    tile = 16
    opts = DGLMNETOptions(tile=tile, max_iters=40)
    kw = dict(path_len=args.path_len, screen=True)
    base = LogisticL1(opts=opts, mesh=work_mesh).path(
        as_design(slabs, mesh=work_mesh, tile=tile), y, **kw)

    sizing = as_design(slabs, mesh=work_mesh, tile=tile)
    budget = sizing.slab_nbytes(tile) - min(sizing.slab_bucket_nbytes(tile))
    opts_s = replace(opts, device_budget_bytes=budget)

    def streamed_design():
        return as_design(slabs, mesh=work_mesh, tile=tile,
                         device_budget_bytes=budget)

    # transient: two consecutive put failures, absorbed by retry (3
    # attempts) — the path must not notice
    with inject_faults(FaultPlan(fail_prefetches=2)):
        des = streamed_design()
        streamed = LogisticL1(opts=opts_s, mesh=work_mesh).path(des, y, **kw)
    stats = des.residency_stats()[tile]
    assert stats["streamed"] and stats["evictions"] > 0, stats
    assert stats["retries"] == 2, stats
    assert np.array_equal(np.asarray(streamed.betas), np.asarray(base.betas))
    assert np.array_equal(streamed.f, base.f)
    assert np.array_equal(streamed.nnz, base.nnz)

    # fatal: a failure window >= the retry budget, placed after half the
    # healthy run's puts so the path dies mid-solve with checkpoints down
    with tempfile.TemporaryDirectory() as d:
        ckpt = dict(checkpoint_every=1, resume_from=d)
        died = False
        try:
            with inject_faults(FaultPlan(
                    fail_prefetches=3,
                    fail_prefetches_after=stats["puts"] // 2)):
                LogisticL1(opts=opts_s, mesh=work_mesh).path(
                    streamed_design(), y, **ckpt, **kw)
        except RetriesExhausted:
            died = True
        assert died, "fatal prefetch window never fired"
        resumed = LogisticL1(opts=opts_s, mesh=work_mesh).path(
            streamed_design(), y, **ckpt, **kw)
    assert np.array_equal(np.asarray(resumed.betas), np.asarray(base.betas))
    assert np.array_equal(resumed.f, base.f)
    assert np.array_equal(resumed.nnz, base.nnz)
    print(f"# lost-bucket: streamed {stats['n_buckets']} buckets under "
          f"budget {budget}B (hit_rate={stats['hit_rate']:.2f}, "
          f"evictions={stats['evictions']}), transient faults retried, "
          f"fatal window after {stats['puts'] // 2} puts resumed "
          f"bit-identically")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="all",
                    choices=_SCENARIOS + ("all",))
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes (the CI chaos lane)")
    ap.add_argument("--mesh", default="local",
                    help="'local' (default) or a mesh spec like '2x4'")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--p", type=int, default=128)
    ap.add_argument("--path-len", type=int, default=4)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run under repro.obs, assert each scenario's "
                         "expected faults.*/retry.* counters fired, and "
                         "write PATH.trace.json / PATH.events.jsonl / "
                         "PATH.summary.json")
    args = ap.parse_args()
    if args.smoke:
        args.n, args.p, args.path_len = min(args.n, 128), min(args.p, 64), \
            min(args.path_len, 3)

    mesh = None
    if args.mesh != "local":
        from repro.launch.mesh import parse_mesh

        mesh = parse_mesh(args.mesh)

    todo = _SCENARIOS if args.scenario == "all" else (args.scenario,)
    if args.trace is None:
        for name in todo:
            globals()["scenario_" + name.replace("-", "_")](args, mesh)
    else:
        with observe() as obs:
            for name in todo:
                globals()["scenario_" + name.replace("-", "_")](args, mesh)
                for cname in _EXPECT[name]:
                    got = obs.registry.value(cname)
                    if not got:
                        raise SystemExit(
                            f"FAIL: scenario {name} ran under --trace but "
                            f"counter {cname} never fired (value={got})")
                print(f"# trace: {name} fault counters fired: " + ", ".join(
                    f"{c}={obs.registry.value(c)}" for c in _EXPECT[name]))
        summary = obs.summary()
        dumped = summary.get("counters", {})
        for name in todo:
            for cname in _EXPECT[name]:
                if not dumped.get(cname):
                    raise SystemExit(
                        f"FAIL: counter {cname} fired live but is missing "
                        f"from the summary dump")
        files = obs.export(args.trace)
        print(f"# trace: {files['trace']} (open in Perfetto) | "
              f"summary: {files['summary']} "
              f"(python -m repro.obs.report {files['summary']})")
    if args.smoke:
        print("CHAOS SMOKE OK")


if __name__ == "__main__":
    main()
