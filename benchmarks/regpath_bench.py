"""Regularization-path timing: seed-style host loop vs the device-resident
screened engine. Emits ``BENCH_regpath.json``.

Two drivers over the identical warm-started lambda grid (Algorithm 5):

* **seed-style** — the seed's Python outer loop (`fit_python_loop`): one
  objective sync per outer iteration, full-p subproblems at every lambda.
* **engine** — `regularization_path(screen=True)`: jitted while_loop solves
  (core/engine.py) restricted to the strong-rule/KKT active set
  (core/screening.py), capacity-bucketed so the whole path reuses a
  handful of compilations.

Both sides are run once to compile (cold) and once compiled (warm); the
headline comparison — and the CI gate — is warm wall-clock, which is what
repeated production paths pay.

``--distributed`` adds a third driver — ``regularization_path_distributed``
on a 2x4 fake-device mesh (same screened engine, restricted solves on the
mesh); ``--sparse`` runs it over by-feature (row_idx, values) slabs so the
whole path (screen included) never materializes a dense X. ``--streamed``
adds the HBM-budgeted residency section: the same slab-bucket path with
``device_budget_bytes`` one bucket short of the padded slab total, so the
``BucketResidencyManager`` double-buffers buckets host->device through
every pass — reported against the resident run (warm ratio, prefetch hit
rate) with a bit-identity check. ``--cycle``
adds the blocked-vs-sequential CD cycle section: a per-tile microbench of
the semi-parallel cycle against the F-step chain plus the engine path
rerun with ``cycle_mode="blocked"`` (the CI gate keeps the per-tile
speedup from collapsing — the chain silently re-serializing). ``--serve``
adds the online path-serving section (``repro.serve`` throughput at two
batch sizes; gated catastrophic-only).

    PYTHONPATH=src python -m benchmarks.regpath_bench            # paper-ish shape
    PYTHONPATH=src python -m benchmarks.regpath_bench --tiny     # CI smoke
    PYTHONPATH=src python -m benchmarks.regpath_bench --tiny --distributed --sparse --streamed --kernels --cycle --serve
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

if "--distributed" in sys.argv:
    # the fake-device flag must land before the first jax import; an
    # inherited count below 8 can't be overridden here, so fail loudly
    # instead of letting make_dev_mesh(2, 4) error opaquely later
    _flags = os.environ.get("XLA_FLAGS", "")
    _m = re.search(r"--xla_force_host_platform_device_count=(\d+)", _flags)
    if _m is None:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        )
    elif int(_m.group(1)) < 8:
        sys.exit(
            f"--distributed needs >= 8 fake devices but XLA_FLAGS already "
            f"forces {_m.group(1)}; unset XLA_FLAGS or raise the count"
        )

import jax
import jax.numpy as jnp

from repro.configs.base import GLMConfig
from repro.core import DGLMNETOptions, fit_python_loop, lambda_max, regularization_path
from repro.data.synthetic import make_glm_dataset
from repro.launch.compile_cache import enable_compile_cache


def seed_style_path(X, y, path_len: int, opts: DGLMNETOptions):
    """The seed's path driver: warm-started loop of host-driven fits."""
    lmax = float(lambda_max(X, y))
    beta = None
    rows = []
    for i in range(1, path_len + 1):
        lam = lmax * 2.0 ** (-i)
        res = fit_python_loop(X, y, lam, beta0=beta, opts=opts)
        beta = res.beta
        rows.append({"lam": lam, "nnz": res.nnz, "f": res.f,
                     "n_iters": res.n_iters})
    return rows


def frontdoor_path(X, y, path_len: int, opts: DGLMNETOptions):
    """The screened engine path through the ``repro.api`` front door
    (``LogisticL1.path`` — what ``regularization_path`` now shims to)."""
    from repro.api import DenseDesign, LogisticL1

    pts = LogisticL1(opts=opts).path(DenseDesign(X), y, path_len=path_len,
                                     screen=True)
    return [{"lam": p.lam, "nnz": p.nnz, "f": p.f, "n_iters": p.n_iters,
             **{f"screen_{k}": v for k, v in p.screen.items()}} for p in pts]


def distributed_path(data, y, path_len: int, opts: DGLMNETOptions, mesh):
    from repro.core import regularization_path_distributed

    pts = regularization_path_distributed(data, y, mesh, path_len=path_len,
                                          opts=opts)
    return [{"lam": p.lam, "nnz": p.nnz, "f": p.f, "n_iters": p.n_iters,
             **{f"screen_{k}": v for k, v in p.screen.items()}} for p in pts]


def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def bench_serve(X, y, path_len: int, opts: DGLMNETOptions,
                batch_sizes=(64, 256), steps: int = 20) -> dict:
    """Online-scoring throughput of the serving layer (``repro.serve``):
    a certified path published into a ``PathStore``, synthetic hashed-
    token traffic through the batcher, one jitted ``slab_path_spmv``
    dispatch per drain. Reported per batch size — scores/sec is the
    serving headline the CI gate floors (catastrophic-only: throughput
    rides host-side packing and flaps more than path wall-clock) plus
    the submit->score latency histogram (p50/p95/p99 seconds) recorded
    through ``repro.obs``."""
    import numpy as np

    from repro.api import DenseDesign, LogisticL1
    from repro.launch.serve_glm import make_traffic, serve_loop
    from repro.obs import observe
    from repro.serve import PathScorer, PathStore, RequestBatcher

    path = LogisticL1(opts=opts).path(DenseDesign(X), y, path_len=path_len)
    p = X.shape[1]
    scorer = PathScorer(PathStore(path))
    rng = np.random.default_rng(0)
    out = {"path_len": len(path), "p": p, "batch": {}}
    for bs in batch_sizes:
        batcher = RequestBatcher(p, max_batch=bs)
        reqs, lams = make_traffic(rng, p, bs * steps, path.lambdas)
        for r, lam in zip(reqs[:bs], lams[:bs]):   # compile warm-up drain
            batcher.submit(r, lam)
        scorer.score(*batcher.drain())
        # each batch size gets its own observe() window so the
        # submit->score latency histogram (fed by mark_scored inside
        # serve_loop) is per-row, not cumulative across sizes
        with observe() as obs:
            total, secs, _ = serve_loop(scorer, batcher, reqs, lams,
                                        steps=steps)
        hist = obs.summary().get("histograms", {}).get("serve.latency_s") \
            or {}
        out["batch"][str(bs)] = {
            "scored": total, "warm_s": secs,
            "scores_per_s": total / max(secs, 1e-12),
            "latency_s": {k: hist.get(k)
                          for k in ("p50", "p95", "p99", "count")},
        }
    return out


def bench_streamed(n: int, p: int, path_len: int, opts: DGLMNETOptions,
                   mesh, dp: int) -> dict:
    """Streamed (HBM-budgeted) vs resident slab-bucket path at matched
    shapes: the same screened driver over the same ``SlabBuckets``, once
    fully device-resident and once with ``device_budget_bytes`` one
    bucket short of the padded total, so the residency manager must
    double-buffer host->device through every pass. Reports the
    streamed/resident warm ratio (the price of not fitting in HBM), the
    prefetch hit rate, and a bit-identity check — streaming changes
    where buckets live, never the math.

    The section rebuilds its own stratified-density X: uniform-density
    columns land in one or two nnz capacity classes, and with fewer than
    three buckets the double buffer already covers the slab (nothing to
    evict, nothing to stream)."""
    import numpy as np

    from repro.api import LogisticL1, as_design
    from repro.data.byfeature import to_by_feature, to_slab_buckets

    rng = np.random.default_rng(0)
    levels = [4, 12, 28, min(60, n // 2)]
    X = np.zeros((n, p), np.float32)
    for j in range(p):
        rows = rng.choice(n, size=levels[j % len(levels)], replace=False)
        X[rows, j] = rng.normal(size=rows.size).astype(np.float32)
    w = rng.normal(size=p) * (rng.random(p) < 0.3)
    prob = 1.0 / (1.0 + np.exp(-(X @ w)))
    y = np.where(rng.random(n) < prob, 1.0, -1.0).astype(np.float32)

    slabs = to_slab_buckets(to_by_feature(X), dp)
    assert len(slabs.buckets) >= 3, slabs.k_classes
    tile = opts.tile
    sizing = as_design(slabs, mesh=mesh, tile=tile)
    budget = sizing.slab_nbytes(tile) - min(sizing.slab_bucket_nbytes(tile))
    last = {}

    def run_path(budget_bytes):
        # a fresh design per call: resident timing pays its one-shot
        # device puts the same way streamed pays per-pass streaming, so
        # the warm ratio compares end-to-end placement + solve
        des = as_design(slabs, mesh=mesh, tile=tile,
                        device_budget_bytes=budget_bytes)
        pts = LogisticL1(opts=opts, mesh=mesh).path(des, y,
                                                    path_len=path_len)
        last["des"] = des
        last["pts"] = pts
        return [pt.beta for pt in pts]

    _, res_cold = _timed(lambda: run_path(None))
    _, res_warm = _timed(lambda: run_path(None))
    res_pts = last["pts"]
    _, str_cold = _timed(lambda: run_path(budget))
    _, str_warm = _timed(lambda: run_path(budget))
    stats = last["des"].residency_stats()[tile]
    assert stats["streamed"] and stats["evictions"] > 0, stats
    bit_identical = all(
        a.lam == b.lam and a.f == b.f and a.nnz == b.nnz
        and bool(jnp.all(a.beta == b.beta))
        for a, b in zip(res_pts, last["pts"]))
    return {
        "n_buckets": stats["n_buckets"],
        "budget_bytes": stats["budget_bytes"],
        "total_bytes": stats["total_bytes"],
        "resident_cold_s": res_cold, "resident_warm_s": res_warm,
        "streamed_cold_s": str_cold, "streamed_warm_s": str_warm,
        "warm_ratio_streamed_vs_resident": str_warm / max(res_warm, 1e-12),
        "prefetch": {k: stats[k] for k in ("hits", "misses", "evictions",
                                           "puts", "bytes_h2d",
                                           "hit_rate")},
        "bit_identical": bit_identical,
    }


def run(*, n: int = 2048, p: int = 4096, path_len: int = 20,
        density: float = 0.2, k_true: int = 64,
        out_path: str = "BENCH_regpath.json",
        distributed: bool = False, sparse: bool = False,
        streamed: bool = False,
        kernels: bool = False, cycle: bool = False, block: int = 16,
        serve: bool = False, tiny: bool = False,
        trace_summary: str = None) -> dict:
    # sparse ground truth (k_true << p): the large-p regime screening is
    # for — most features never activate anywhere on the path
    cfg = GLMConfig(name="regpath-bench", num_examples=int(n / 0.8),
                    num_features=p, density=density)
    ds = make_glm_dataset(cfg, jax.random.key(0), k_true=k_true)
    X, y = ds.X_train, ds.y_train
    opts = DGLMNETOptions(num_blocks=8, tile=128, max_iters=40)
    print(f"# regpath bench: n={X.shape[0]} p={X.shape[1]} "
          f"path_len={path_len} density={density}")

    seed_rows, seed_cold = _timed(lambda: seed_style_path(X, y, path_len, opts))
    _, seed_warm = _timed(lambda: seed_style_path(X, y, path_len, opts))
    eng_rows, eng_cold = _timed(lambda: frontdoor_path(X, y, path_len, opts))
    _, eng_warm = _timed(lambda: frontdoor_path(X, y, path_len, opts))

    if trace_summary:
        # one extra warm front-door leg under repro.obs: the summary's
        # per-phase totals (screen_round / restricted_solve / kkt_check /
        # point_finish) let compare_bench explain a warm-path regression
        # by phase instead of one opaque wall number
        from repro.obs import observe, write_summary

        with observe() as obs:
            _, traced_warm = _timed(
                lambda: frontdoor_path(X, y, path_len, opts))
        summary = obs.summary()
        summary["bench"] = {"section": "frontdoor",
                            "traced_warm_s": traced_warm}
        write_summary(summary, trace_summary)
        print(f"# trace summary: {trace_summary} "
              f"(traced warm {traced_warm:.2f}s; "
              f"python -m repro.obs.report {trace_summary})")

    report = {
        "config": {"n": int(X.shape[0]), "p": int(X.shape[1]),
                   "path_len": path_len, "density": density, "k_true": k_true,
                   "opts": {"num_blocks": opts.num_blocks, "tile": opts.tile,
                            "max_iters": opts.max_iters}},
        "seed_style": {"cold_s": seed_cold, "warm_s": seed_warm,
                       "per_lambda": seed_rows},
        # renamed from "engine" when the path moved behind the repro.api
        # front door; compare_bench accepts either name so the checked-in
        # baselines stay valid
        "frontdoor": {"cold_s": eng_cold, "warm_s": eng_warm,
                      "per_lambda": eng_rows},
        "speedup_warm": seed_warm / max(eng_warm, 1e-12),
        "speedup_cold": seed_cold / max(eng_cold, 1e-12),
        "frontdoor_strictly_faster": eng_warm < seed_warm,
    }
    if distributed:
        from repro.launch.mesh import make_dev_mesh

        mesh = make_dev_mesh(2, 4)
        n_trim = (X.shape[0] // 2) * 2
        Xd, yd = X[:n_trim], y[:n_trim]
        if sparse:
            from repro.data.byfeature import to_by_feature, to_slabs

            row_idx, values, _ = to_slabs(to_by_feature(Xd), 2)
            data = (row_idx, values)
        else:
            data = Xd
        dist_rows, dist_cold = _timed(
            lambda: distributed_path(data, yd, path_len, opts, mesh))
        _, dist_warm = _timed(
            lambda: distributed_path(data, yd, path_len, opts, mesh))
        report["distributed"] = {
            "mesh": dict(mesh.shape), "sparse": sparse,
            "cold_s": dist_cold, "warm_s": dist_warm,
            "per_lambda": dist_rows,
        }
        print(f"# distributed{' (sparse slabs)' if sparse else ''}: "
              f"cold {dist_cold:.2f}s warm {dist_warm:.2f}s")
        if streamed:
            report["streamed"] = bench_streamed(n_trim, X.shape[1],
                                                path_len, opts, mesh, 2)
            st = report["streamed"]
            print(f"# streamed: warm {st['streamed_warm_s']:.2f}s vs "
                  f"resident {st['resident_warm_s']:.2f}s "
                  f"({st['warm_ratio_streamed_vs_resident']:.2f}x) under "
                  f"budget {st['budget_bytes']}/{st['total_bytes']}B over "
                  f"{st['n_buckets']} buckets; prefetch hit rate "
                  f"{st['prefetch']['hit_rate']:.2f}; bit_identical="
                  f"{st['bit_identical']}")
    if cycle:
        import dataclasses

        from benchmarks.kernels_bench import bench_cycle_tile

        # the engine path again, with every within-tile chain swapped for
        # the blocked semi-parallel cycle — same screened driver, so the
        # warm delta is exactly the chain-vs-blocked difference
        blk_opts = dataclasses.replace(opts, cycle_mode="blocked",
                                       block=block)
        blk_rows, blk_cold = _timed(lambda: frontdoor_path(X, y, path_len,
                                                        blk_opts))
        _, blk_warm = _timed(lambda: frontdoor_path(X, y, path_len, blk_opts))
        # acceptance: the blocked path must land on the sequential path's
        # objectives — the safeguard + line search make it an acceleration,
        # not an approximation
        max_gap = max(
            abs(b["f"] - s["f"]) / max(abs(s["f"]), 1e-9)
            for b, s in zip(blk_rows, eng_rows)
        )
        # the microbench is the gate: fixed canonical shapes in CI and
        # locally (like the slab suite — the gate needs the regime where
        # the blocked win is decisive, which tiny path shapes can't
        # provide: a 32-row tile is rank-deficient and the safeguard
        # rightly refuses to parallelize it), and reps stay high (the
        # cycle is ~30us — a flaky floor would be worse than a slow one)
        report["cycle"] = {
            "block": block,
            # bench-shape tile: F=128 from n_loc=2048 density-0.2 rows
            "per_tile": bench_cycle_tile(f=128, n_loc=2048, block=block,
                                         reps=30),
            # production-mesh-depth tile: n_loc = 2048/16 (16x16 mesh data
            # extent). Informational, not gated: at this depth the Gram
            # tile is near rank-deficient and the Gershgorin safeguard
            # demotes most blocks — the entry tracks how the safeguard
            # behaves, not a speedup floor.
            "per_tile_mesh16": bench_cycle_tile(f=128, n_loc=128,
                                               block=block, reps=30),
            "path": {"cold_s": blk_cold, "warm_s": blk_warm,
                     "sequential_warm_s": eng_warm,
                     "speedup_vs_sequential": eng_warm / max(blk_warm, 1e-12),
                     "max_rel_f_gap": max_gap,
                     "per_lambda": blk_rows},
        }
        for key in ("per_tile", "per_tile_mesh16"):
            pt = report["cycle"][key]
            print(f"# cycle {key} (n_loc={pt['n_loc']}): cycle "
                  f"{pt['blocked_us']:.0f}us vs {pt['sequential_us']:.0f}us "
                  f"({pt['speedup']:.2f}x); tile step "
                  f"{pt['step_blocked_us']:.0f}us vs "
                  f"{pt['step_sequential_us']:.0f}us "
                  f"({pt['step_speedup']:.2f}x); modes={pt['modes']}")
        print(f"# cycle path: warm {blk_warm:.2f}s vs {eng_warm:.2f}s "
              f"sequential (max rel f gap {max_gap:.1e})")
    if kernels:
        from benchmarks.kernels_bench import bench_slab_suite

        # same shapes in CI and locally: the gate needs the regime where
        # the sparse-native win is decisive (a densify regression reads as
        # speedup ~1x, which tiny shapes cannot distinguish from noise);
        # fewer reps keep the tiny budget
        report["kernels"] = bench_slab_suite(reps=5 if tiny else 10)
        for name, row in report["kernels"].items():
            if isinstance(row, dict):
                print(f"# kernel {name}: sparse {row['sparse_us']:.0f}us "
                      f"vs densify {row['densify_us']:.0f}us "
                      f"({row['speedup']:.2f}x)")
    if serve:
        report["serve"] = bench_serve(X, y, path_len, opts,
                                      steps=10 if tiny else 30)
        for bs, row in report["serve"]["batch"].items():
            lat = row["latency_s"]
            lat_txt = ""
            if lat.get("count"):
                lat_txt = (f"; latency p50 {lat['p50'] * 1e3:.2f}ms / "
                           f"p95 {lat['p95'] * 1e3:.2f}ms / "
                           f"p99 {lat['p99'] * 1e3:.2f}ms")
            print(f"# serve batch {bs}: {row['scores_per_s']:,.0f} "
                  f"scores/sec ({row['scored']} in {row['warm_s']:.3f}s)"
                  + lat_txt)
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    print(f"# seed-style: cold {seed_cold:.2f}s warm {seed_warm:.2f}s")
    print(f"# frontdoor:  cold {eng_cold:.2f}s warm {eng_warm:.2f}s")
    print(f"# warm speedup {report['speedup_warm']:.2f}x "
          f"(strictly faster: {report['frontdoor_strictly_faster']})")
    print(f"# wrote {out_path}")
    return report


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke shapes (seconds, not minutes)")
    ap.add_argument("--distributed", action="store_true",
                    help="also time regularization_path_distributed on a "
                         "2x4 fake-device mesh")
    ap.add_argument("--sparse", action="store_true",
                    help="with --distributed: run over by-feature sparse "
                         "slabs (no dense X on the mesh path)")
    ap.add_argument("--streamed", action="store_true",
                    help="with --distributed: add the HBM-budgeted "
                         "streamed-residency section (streamed vs "
                         "resident warm path, prefetch hit rate, "
                         "bit-identity)")
    ap.add_argument("--kernels", action="store_true",
                    help="add the slab kernel microbench section "
                         "(sparse-native vs densify at matched shapes)")
    ap.add_argument("--cycle", action="store_true",
                    help="add the blocked-vs-sequential CD cycle section "
                         "(per-tile microbench + blocked end-to-end warm "
                         "path)")
    ap.add_argument("--block", type=int, default=16,
                    help="B: coordinates per semi-parallel block for "
                         "--cycle (default 16)")
    ap.add_argument("--serve", action="store_true",
                    help="add the online path-serving section (scores/sec "
                         "through repro.serve at two batch sizes)")
    ap.add_argument("--out", default="BENCH_regpath.json")
    ap.add_argument("--trace-summary", default=None, metavar="PATH",
                    help="re-run the warm front-door leg under repro.obs "
                         "and write its per-phase summary JSON to PATH "
                         "(render with python -m repro.obs.report; feed "
                         "to compare_bench --fresh-trace)")
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--p", type=int, default=4096)
    ap.add_argument("--path-len", type=int, default=20)
    ap.add_argument("--density", type=float, default=0.2)
    args = ap.parse_args()
    if args.tiny:
        args.n, args.p, args.path_len = 512, 256, 6
    if args.sparse and not args.distributed:
        ap.error("--sparse requires --distributed")
    if args.streamed and not args.distributed:
        ap.error("--streamed requires --distributed")
    report = run(n=args.n, p=args.p, path_len=args.path_len,
                 density=args.density, out_path=args.out,
                 distributed=args.distributed, sparse=args.sparse,
                 streamed=args.streamed,
                 kernels=args.kernels, cycle=args.cycle, block=args.block,
                 serve=args.serve, tiny=args.tiny,
                 trace_summary=args.trace_summary)
    # Screening pays in proportion to p; tiny CI-smoke shapes sit below the
    # break-even point, so the strictly-faster gate applies to real shapes.
    if not args.tiny and not report["frontdoor_strictly_faster"]:
        raise SystemExit("FAIL: front-door path not strictly faster than seed-style")


if __name__ == "__main__":
    main()
