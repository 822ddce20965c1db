"""GLM path-serving launcher: batched online scoring of a certified path.

    PYTHONPATH=src python -m repro.launch.serve_glm --smoke
    PYTHONPATH=src python -m repro.launch.serve_glm --smoke --mesh 2x4
    PYTHONPATH=src python -m repro.launch.serve_glm --load-path ckpt/ \
        --batch 256 --steps 50

Fits (or loads via ``--load-path``, see ``PathResult.save``) a certified
regularization path, publishes it into a device-resident
:class:`repro.serve.PathStore`, then drives synthetic hashed-token request
traffic through the :class:`RequestBatcher` -> :class:`PathScorer` loop —
one jitted slab dispatch per batch, every request row picking its own
lambda — and reports scores/sec. ``--smoke`` additionally self-checks
served scores bit-equal to ``LogisticL1.decision_function`` at every
operating point and exercises a hot-swap mid-traffic.

``--trace PATH`` runs the whole launcher under ``repro.obs.observe()``
and writes ``PATH.trace.json`` (Perfetto-loadable), ``PATH.events.jsonl``
and ``PATH.summary.json`` — the summary carries the submit->score
latency histogram (p50/p95/p99) and the serve/drain/score/swap span
totals; render it with ``python -m repro.obs.report PATH.summary.json``.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import time

if "--mesh" in sys.argv:
    # fake-device flag must land before the first jax import (same dance
    # as benchmarks.regpath_bench); fail loudly on an unraisable count
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

from repro.api import LogisticL1, PathResult, SlabDesign, ShardedDesign
from repro.configs.base import GLMConfig
from repro.data.synthetic import make_glm_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import observe, trace as obs_trace
from repro.serve import PathScorer, PathStore, RequestBatcher, hash_token


def make_traffic(rng, p: int, count: int, lambdas, *, tokens_per: int = 12):
    """Synthetic hashed-token requests + per-request lambda picks."""
    reqs, lams = [], []
    for _ in range(count):
        k = int(rng.integers(1, tokens_per + 1))
        toks = rng.integers(0, 4 * p, size=k)
        reqs.append({f"tok{t}": float(v)
                     for t, v in zip(toks, rng.normal(size=k))})
        lams.append(float(lambdas[int(rng.integers(0, len(lambdas)))]))
    return reqs, lams


def serve_loop(scorer, batcher, reqs, lams, *, steps: int):
    """Drive ``steps`` drain->score rounds over the traffic; returns
    (total scores, elapsed seconds, versions seen).

    Under an active ``repro.obs`` tracer the rounds run inside a
    ``serve`` span (the encode/drain/score spans come from the serve
    layer itself), and each scored drain feeds the submit->score
    ``serve.latency_s`` histogram via :meth:`RequestBatcher.mark_scored`
    — called right after ``scorer.score`` returns host numpy, the
    loop's existing sync point."""
    total, versions = 0, set()
    per = max(1, len(reqs) // steps)
    t0 = time.perf_counter()
    with obs_trace.span("serve", steps=steps):
        for s in range(steps):
            for r, l in zip(reqs[s * per:(s + 1) * per],
                            lams[s * per:(s + 1) * per]):
                batcher.submit(r, l)
            batch, blams = batcher.drain()
            scores, ver = scorer.score(batch, blams)
            batcher.mark_scored()
            total += len(scores)
            versions.add(ver)
    # allow[bench-timing]: scorer.score returns host numpy — every batch is synced before the clock stops
    return total, time.perf_counter() - t0, versions


#: served-vs-``decision_function`` bound where the two sum a row's terms
#: in different orders (the local path on a TPU): the float32 limit on
#: |served - reference| / sum |beta v| that the serving benchmark holds
#: every served score to
SCORE_GAP = 1e-5


def smoke_check(est, store, scorer, batch, n_live: int, path) -> None:
    """Served scores against ``decision_function`` on the batch's slabs at
    every lambda: bit-equal where both sum each row in the same order (on
    the CPU, and through the mesh branch, which runs the same slab
    kernel); on a TPU's local path, which sums the entry list in another
    order, within ``SCORE_GAP`` of sum |beta v| per row."""
    inner = SlabDesign(jnp.asarray(batch.row_idx),
                       jnp.asarray(batch.values), batch.batch_cap)
    design = (ShardedDesign(inner, store.mesh, tile=store.tile)
              if store.mesh is not None else inner)
    exact = store.mesh is not None or jax.default_backend() != "tpu"
    n = batch.n_entries
    worst = 0.0
    for l in range(len(path)):
        beta = path.betas[l]
        if batch.p_pad != beta.shape[0]:
            beta = jnp.pad(beta, (0, batch.p_pad - beta.shape[0]))
        # allow[nonfinite-guard]: decision_function is the reference oracle; the served side of the comparison IS the guarded path
        ref = np.asarray(est.decision_function(design, beta=beta))[:n_live]
        got, _ = scorer.score(batch, np.full(n_live, path.lambdas[l]))
        if exact:
            if not np.array_equal(got, ref):
                raise SystemExit(
                    f"FAIL: served scores not bit-equal to decision_function "
                    f"at lambda index {l} "
                    f"(max |diff| {np.max(np.abs(got - ref)):.3e})")
            continue
        terms = np.abs(np.asarray(beta, np.float64)[batch.entry_feat[:n]]
                       * batch.entry_val[:n])
        scale = np.bincount(batch.entry_row[:n], weights=terms,
                            minlength=batch.batch_cap)[:n_live]
        gap = np.abs(got.astype(np.float64) - ref)
        worst = max(worst, float(np.max(gap)))
        if np.any(gap > SCORE_GAP * scale):
            raise SystemExit(
                f"FAIL: served scores differ from decision_function by more "
                f"than {SCORE_GAP} of sum |beta v| at lambda index {l} "
                f"(max |diff| {np.max(gap):.3e})")
    how = ("bit-equal to" if exact else
           f"within {SCORE_GAP} of sum |beta v| (max |diff| {worst:.3e}) of")
    print(f"# smoke: served scores {how} decision_function at all "
          f"{len(path)} lambdas")


def hot_swap_check(store, scorer, batch, lams, path) -> None:
    """Publish a truncated path mid-traffic: the next batch must score
    against exactly the new version, without dropping a row."""
    sub = PathResult(lambdas=path.lambdas[:2], betas=path.betas[:2],
                     nnz=path.nnz[:2], f=path.f[:2],
                     n_iters=path.n_iters[:2], metrics=path.metrics[:2],
                     screen=path.screen[:2])
    v_before = scorer.score(batch, lams)[1]
    store.swap(sub)
    got, v_after = scorer.score(batch, lams)
    if v_after != v_before + 1 or len(got) != batch.n_live:
        raise SystemExit("FAIL: hot-swap version bookkeeping broken")
    print(f"# smoke: hot-swap v{v_before} -> v{v_after} served "
          f"{len(got)} scores without dropping the batch")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + bit-equality and hot-swap "
                         "self-checks")
    ap.add_argument("--mesh", default="local",
                    help="'local' (default) or a mesh spec like '2x4' "
                         "(P(model)-sharded coefficient stack)")
    ap.add_argument("--batch", type=int, default=64,
                    help="max requests per scoring dispatch")
    ap.add_argument("--steps", type=int, default=20,
                    help="drain->score rounds to time")
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--p", type=int, default=512)
    ap.add_argument("--path-len", type=int, default=6)
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--save-path", default=None,
                    help="directory to PathResult.save the fitted path")
    ap.add_argument("--load-path", default=None,
                    help="serve a PathResult.save checkpoint instead of "
                         "fitting (no training data touched)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run under repro.obs and write PATH.trace.json "
                         "(Perfetto-loadable) / PATH.events.jsonl / "
                         "PATH.summary.json with span totals and the "
                         "submit->score latency histogram")
    args = ap.parse_args()
    if args.smoke:
        args.n, args.p, args.path_len = min(args.n, 256), min(args.p, 128), \
            min(args.path_len, 4)

    mesh = None
    if args.mesh != "local":
        from repro.launch.mesh import parse_mesh

        mesh = parse_mesh(args.mesh)

    if args.trace is None:
        _run(args, mesh)
        return
    with observe() as obs:
        _run(args, mesh)
    summary = obs.summary()
    hist = summary.get("histograms", {}).get("serve.latency_s")
    if hist and hist["count"]:
        print(f"# submit->score latency ({hist['count']} requests): "
              f"p50 {hist['p50'] * 1e3:.2f}ms / "
              f"p95 {hist['p95'] * 1e3:.2f}ms / "
              f"p99 {hist['p99'] * 1e3:.2f}ms")
    files = obs.export(args.trace)
    print(f"# trace: {files['trace']} (open in Perfetto) | "
          f"summary: {files['summary']} "
          f"(python -m repro.obs.report {files['summary']})")


def _run(args, mesh):
    est = LogisticL1(mesh=mesh) if mesh is not None else LogisticL1()
    if args.load_path:
        path = PathResult.load(args.load_path)
        print(f"# loaded path: L={len(path)} p={path.betas.shape[1]} "
              f"from {args.load_path}")
    else:
        cfg = GLMConfig(name="serve-glm", num_examples=args.n,
                        num_features=args.p, density=0.1)
        ds = make_glm_dataset(cfg, jax.random.key(0))
        X, y = ds.X_train, ds.y_train
        if mesh is not None:
            from repro.core.distributed import _data_extent

            n_trim = (X.shape[0] // _data_extent(mesh)) * _data_extent(mesh)
            X, y = X[:n_trim], y[:n_trim]
        path = est.path(X, y, path_len=args.path_len)
        print(f"# fitted path: L={len(path)} p={args.p} "
              f"nnz={path.nnz.tolist()}")
    if args.save_path:
        path.save(args.save_path)
        print(f"# saved path to {args.save_path}")

    store = PathStore(path, mesh=mesh, tile=args.tile)
    scorer = PathScorer(store)
    p = store.snapshot.p
    dp = 1
    if mesh is not None:
        from repro.core.distributed import _data_extent

        dp = _data_extent(mesh)
    batcher = RequestBatcher(p, max_batch=args.batch, dp=dp,
                             pad_p_to=store.pad_p_to)

    rng = np.random.default_rng(0)
    reqs, lams = make_traffic(rng, p, args.batch * args.steps, path.lambdas)

    # warm the compiled program, then time
    for r, l in zip(reqs[:args.batch], lams[:args.batch]):
        batcher.submit(r, l)
    warm_batch, warm_lams = batcher.drain()
    scorer.score(warm_batch, warm_lams)

    total, secs, versions = serve_loop(scorer, batcher, reqs, lams,
                                       steps=args.steps)
    rate = total / max(secs, 1e-12)
    print(f"# served {total} scores in {secs:.3f}s -> {rate:,.0f} "
          f"scores/sec (batch<= {args.batch}, mesh={args.mesh})")

    if args.smoke:
        smoke_check(est if args.load_path is None else LogisticL1(mesh=mesh),
                    store, scorer, warm_batch, warm_batch.n_live, path)
        hot_swap_check(store, scorer, warm_batch, warm_lams, path)
        print("SERVE SMOKE OK")


if __name__ == "__main__":
    main()
