#!/usr/bin/env python3
"""Run the certified L1-logistic path and path serving on a TPU chip.

    python chip_smoke.py             # one chip: fit, serve, sparse slab path
    python chip_smoke.py --chips 4   # 2x2 data x model mesh against one chip

One process drives every phase through the front door
(``repro.api.LogisticL1``, ``repro.serve``). The first line names the
device; on any platform but ``tpu`` the script exits non-zero before any
work. Every check raises on failure, so the script exits non-zero; the
last line of stdout, ``{"ok": true, "device": {...}}``, is printed only
when every phase passed. Data is synthetic, made from ``--seed``.

One chip (default):

* **fit** -- the paper's epsilon configuration (``configs.glm.GLM_EPSILON``)
  at full width: 400,000 x 2,000 dense float32 (3.2 GB on the device),
  320,000 train rows. A ``PATH_LEN``-point certified path through
  ``LogisticL1.path(DenseDesign(X), y)``; every point must be status OK
  and KKT-certified with a finite objective, and an independent KKT check
  at ``precision=HIGHEST`` must hold. At the smallest lambda the objective
  must agree with the plain float32 reference ``fit_python_loop`` run at
  the same lambda on the same chip, within ``OBJ_RTOL``.
* **serve** -- the fitted path in a ``PathStore``; a few hundred hashed
  requests spread over every lambda through ``RequestBatcher`` and
  ``PathScorer`` (``slab_path_spmv`` on the native ``slab_spmv`` Pallas
  kernel); served scores bit-equal to ``decision_function`` at every
  lambda; one hot swap.
* **sparse** -- a webspam-density twin through
  ``ShardedDesign(SlabDesign.from_dense(X), mesh)`` on a 1x1 data x model
  mesh of the real device: the path on which ``slab_gram`` and
  ``slab_spmv`` dispatch natively. Its objectives must agree with the same
  data's ``DenseDesign`` path within ``OBJ_RTOL``.

Cuts of the sparse twin from webspam (``configs.glm.GLM_WEBSPAM``:
350,000 examples, 16.6M features, 3,727 nnz per example):

* features 16.6M -> 2,048, so that the dense copy the comparison needs
  fits one chip next to the slabs;
* examples 350,000 -> 200,000 (160,000 train rows);
* density kept at 3,727 / 16.6M = 2.2e-4; it sets K, the nnz per feature
  per data shard (about 35 on one chip, at most 60), so ``8 K^2 <= n_loc``
  holds and the slab kernels, not the densify fallback, serve every
  restricted solve;
* nnz per example therefore falls from 3,727 to about 0.45;
* a uniform Bernoulli sparsity pattern with Gaussian values, so no
  power-law feature skew.

``--chips 4`` runs only what exists across chips: the epsilon dense path
and the sparse slab path on a 2x2 data x model mesh built from
``jax.devices()``, each against the same data's one-chip result on device
0 (objectives within ``OBJ_RTOL``, betas within ``BETA_RTOL``). It prints
the device of every shard of ``X`` and of the design's slab buckets, and
fails if they do not span all four chips.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.api import (  # noqa: E402
    DenseDesign, LogisticL1, ShardedDesign, SlabDesign)
from repro.compat import make_mesh  # noqa: E402
from repro.configs.base import GLMConfig  # noqa: E402
from repro.configs.glm import GLM_EPSILON, GLM_WEBSPAM  # noqa: E402
from repro.core.dglmnet import DGLMNETOptions, fit_python_loop  # noqa: E402
from repro.core.objective import objective  # noqa: E402
from repro.core.screening import _nll_residual  # noqa: E402
from repro.data.byfeature import k_class  # noqa: E402
from repro.data.synthetic import make_glm_dataset  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve_glm import (  # noqa: E402
    hot_swap_check, make_traffic, smoke_check)
from repro.serve import PathScorer, PathStore, RequestBatcher  # noqa: E402
from repro.serve.scoring import _score_local  # noqa: E402

# Each agreement below is judged on objectives recomputed from the
# returned betas over the full design at precision=HIGHEST, so it measures
# the solutions and not the solvers' own margin caches.
#
# OBJ_RTOL: both solves stop at a 1e-6 relative objective decrease per
# outer iteration (DGLMNETOptions.rel_tol), so each stops short of the
# optimum by a small multiple of 1e-6 relative; the float32 objective sum
# over 10^5 examples adds ~1e-5. 1e-4 leaves room for both while any real
# disagreement (a wrong support, a skipped point) moves f by far more.
OBJ_RTOL = 1e-4
# BETA_RTOL: relative L2 distance of two solutions of the same problem.
# At an objective gap g relative to f, the strongly convex (n >> p) loss
# bounds the beta gap by ~sqrt(g); sqrt(1e-4) = 1e-2, doubled for the two
# stopping points.
BETA_RTOL = 2e-2
# KKT_SLACK: every beta_j == 0 must have |grad_j| <= lam * (1 + slack).
# The path certifies this at kkt_tol = 1e-3 outside its working set; zero
# coordinates inside the working set are held only to the solver's
# stopping rule, hence the wider slack.
KKT_SLACK = 1e-2

PATH_LEN = 4
SPARSE_PATH_LEN = 3
TILE = 128
REQUESTS = 384
SERVE_BATCH = 128

# the webspam-density twin of the sparse phases (cuts in the docstring)
SPARSE_TWIN = GLMConfig(
    name="glm-webspam-density-twin",
    citation=GLM_WEBSPAM.citation + ", density kept, scale cut",
    num_examples=200_000, num_features=2048, density=GLM_WEBSPAM.density)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextmanager
def timed(label: str):
    t0 = time.perf_counter()
    yield
    log(f"# time: {label}: {time.perf_counter() - t0:.3f} s")


FAILURES: list = []


def fail(msg: str) -> None:
    """Record a failed check; the run goes on, so that one chip call shows
    every failure, and ends non-zero without the result line."""
    log(f"FAIL: {msg}")
    FAILURES.append(msg)


@contextmanager
def phase(name: str):
    """A phase that raises is a failed check, and the next phase runs."""
    try:
        yield
    except (Exception, SystemExit) as e:  # noqa: BLE001 - reported, then fails the run
        traceback.print_exc()
        fail(f"phase {name} raised {e!r}")


def make_data(cfg, seed: int):
    """Train split (X, y) of the config's synthetic twin; the test split
    is dropped so only the train rows stay on the device."""
    ds = make_glm_dataset(cfg, jax.random.key(seed))
    return ds.X_train, ds.y_train


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def full_objective(X, y, beta, lam: float) -> float:
    """f(beta) over the whole design, margins at precision=HIGHEST."""
    beta = jnp.asarray(np.asarray(beta), jnp.float32)
    m = jnp.dot(X, beta, precision=jax.lax.Precision.HIGHEST)
    return float(objective(m, y, beta, lam))


def kkt_ratio(X, y, beta, lam: float) -> float:
    """max |grad_j| / lam over the coordinates with beta_j == 0."""
    hi = jax.lax.Precision.HIGHEST
    beta = jnp.asarray(np.asarray(beta), jnp.float32)
    g = jnp.dot(X.T, _nll_residual(jnp.dot(X, beta, precision=hi), y),
                precision=hi)
    return float(jnp.max(jnp.where(beta == 0, jnp.abs(g), 0.0))) / lam


def check_path(path, X, y, label: str) -> None:
    """Status OK, KKT-certified and finite at every point, and the
    independent full-design KKT check."""
    if len(path) == 0:
        fail(f"{label}: empty path")
    if not path.all_ok:
        fail(f"{label}: non-OK statuses {path.statuses.tolist()}")
    for i, scr in enumerate(path.screen):
        if scr.get("skipped") or scr.get("degraded") \
                or scr.get("kkt_rounds", 0) < 1:
            fail(f"{label}: point {i} not certified: {scr}")
    if not np.all(np.isfinite(path.f)):
        fail(f"{label}: non-finite objective {path.f.tolist()}")
    ratios = [kkt_ratio(X, y, path.betas[i], float(lam))
              for i, lam in enumerate(path.lambdas)]
    if max(ratios) > 1.0 + KKT_SLACK:
        fail(f"{label}: KKT violated, max|g_j|/lam over beta_j=0 = {ratios}")
    log(f"# {label}: lambdas {[float(v) for v in path.lambdas]}")
    log(f"# {label}: nnz {path.nnz.tolist()} f {path.f.tolist()} "
        f"iters {path.n_iters.tolist()}")
    log(f"# {label}: certified at every point, kkt_rounds "
        f"{[s.get('kkt_rounds') for s in path.screen]}, "
        f"max|g_j|/lam over beta_j=0 (HIGHEST) {ratios}")


def check_agree(X, y, path_a, path_b, label: str, *,
                betas: bool = False) -> None:
    """Objectives (and optionally betas) of two paths at path_a's
    lambdas, both recomputed over the same full design."""
    if len(path_a) != len(path_b):
        fail(f"{label}: path lengths {len(path_a)} != {len(path_b)}")
    for i, lam in enumerate(path_a.lambdas):
        lam = float(lam)
        fa = full_objective(X, y, path_a.betas[i], lam)
        fb = full_objective(X, y, path_b.betas[i], lam)
        gap = abs(fa - fb) / abs(fb)
        msg = f"# {label}: lambda {lam:.6g}: f {fa!r} vs {fb!r}, rel gap {gap:.3e}"
        if betas:
            ba = np.asarray(path_a.betas[i], np.float64)
            bb = np.asarray(path_b.betas[i], np.float64)
            bgap = np.linalg.norm(ba - bb) / max(np.linalg.norm(bb), 1e-30)
            msg += f", beta rel L2 gap {bgap:.3e}"
            if bgap > BETA_RTOL:
                fail(f"{msg} > BETA_RTOL {BETA_RTOL}")
                continue
        if gap > OBJ_RTOL:
            fail(f"{msg} > OBJ_RTOL {OBJ_RTOL}")
        else:
            log(msg)


def assert_native(fn, *args, **static) -> None:
    """The compiled program holds a Mosaic kernel: no interpret mode, no
    jnp fallback."""
    text = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()
    if "tpu_custom_call" not in text:
        fail(f"{getattr(fn, '__name__', fn)} compiled without a TPU kernel")


def shard_devices(arr, label: str) -> set:
    """Print and return the devices holding ``arr``'s shards."""
    devs = set()
    for s in arr.addressable_shards:
        devs.add(s.device.id)
        log(f"# shard: {label} {s.index} on device {s.device.id}")
    return devs


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def fit_phase(X, y, opts):
    """Epsilon path through the front door."""
    est = LogisticL1(opts=opts)
    with timed("fit: epsilon path, first call (compiles included)"):
        path = est.path(DenseDesign(X), y, path_len=PATH_LEN)
    check_path(path, X, y, "fit")
    return est, path


def reference_phase(X, y, opts, path) -> None:
    """The path's smallest-lambda objective against ``fit_python_loop``."""
    lam = float(path.lambdas[-1])
    with timed("fit: reference fit_python_loop, first call"):
        ref = fit_python_loop(X, y, lam, opts=opts)
    f_path = full_objective(X, y, path.betas[-1], lam)
    f_ref = full_objective(X, y, ref.beta, lam)
    gap = abs(f_path - f_ref) / abs(f_ref)
    msg = (f"# fit: reference at lambda {lam:.6g}: path f {f_path!r} vs "
           f"fit_python_loop f {f_ref!r} ({ref.n_iters} iters, nnz "
           f"{ref.nnz}), rel gap {gap:.3e}")
    if gap > OBJ_RTOL:
        fail(f"{msg} > OBJ_RTOL {OBJ_RTOL}")
    else:
        log(msg + f" <= {OBJ_RTOL}")


def serve_phase(est, path, p: int, seed: int) -> None:
    """Hashed traffic over every lambda, bit-equality and one hot swap."""
    store = PathStore(path)
    scorer = PathScorer(store)
    batcher = RequestBatcher(p, max_batch=SERVE_BATCH)
    reqs, lams = make_traffic(np.random.default_rng(seed), p, REQUESTS,
                              path.lambdas)
    hit, shapes = set(), set()
    for b in range(REQUESTS // SERVE_BATCH):
        sl = slice(b * SERVE_BATCH, (b + 1) * SERVE_BATCH)
        for r, lam in zip(reqs[sl], lams[sl]):
            batcher.submit(r, lam)
        batch, blams = batcher.drain()
        t0 = time.perf_counter()
        scores, version = scorer.score(batch, blams)
        dt = time.perf_counter() - t0
        batcher.mark_scored()
        if len(scores) != batch.n_live or not np.all(np.isfinite(scores)):
            fail(f"serve: batch {b} returned {len(scores)} scores "
                 f"for {batch.n_live} requests, or non-finite ones")
        hit |= set(store.snapshot.indices_of(blams).tolist())
        first = batch.row_idx.shape not in shapes   # new shape: compiles
        shapes.add(batch.row_idx.shape)
        log(f"# serve: batch {b} ({'first call' if first else 'warm'}): "
            f"{len(scores)} scores in {dt * 1e3:.3f} ms, slab "
            f"{batch.row_idx.shape}, version {version}")
    if hit != set(range(len(path))):
        fail(f"serve: traffic reached lambdas {sorted(hit)} of {len(path)}")
    assert_native(_score_local, jnp.asarray(batch.row_idx[:, 0, :]),
                  jnp.asarray(batch.values[:, 0, :]),
                  jnp.zeros(batch.batch_cap, jnp.int32),
                  store.snapshot.betas, n_loc=batch.batch_cap)
    log("# serve: scoring program runs the native slab_spmv kernel")
    smoke_check(est, store, scorer, batch, batch.n_live, path)
    hot_swap_check(store, scorer, batch, blams, path)


def slab_design(X, mesh):
    """Sharded by-feature slabs of X, checked to sit in the slab-kernel
    regime (``prefer_slab_gram``) at every K class they produce."""
    with timed("sparse: slabs from dense on host"):
        slab = SlabDesign.from_dense(X, dp=mesh.shape["data"])
    classes = sorted({k_class(int(k), slab.k)
                      for k in slab.k_per_feature()})
    log(f"# sparse: n_loc {slab.n_loc}, K max {slab.k}, K classes {classes}, "
        f"nnz {int((slab.row_idx < slab.n_loc).sum())}")
    if not ops.prefer_slab_gram(slab.n_loc, slab.k):
        fail(f"sparse: K={slab.k} at n_loc={slab.n_loc} would densify")
    return ShardedDesign(slab, mesh, tile=TILE)


def sparse_phase(X, y, opts, mesh) -> None:
    """Slab path on the mesh against the same data's dense path."""
    design = slab_design(X, mesh)
    rows = jnp.zeros((TILE, design.inner.k), jnp.int32)
    vals = jnp.ones((TILE, design.inner.k), jnp.float32)
    w = jnp.ones(design.inner.n_loc, jnp.float32)
    assert_native(ops.slab_gram, rows, vals, w, w)
    assert_native(ops.slab_spmv, rows, vals, w[:TILE],
                  n_loc=design.inner.n_loc)
    log("# sparse: slab_gram and slab_spmv dispatch native kernels")
    with timed("sparse: slab path on the 1x1 mesh, first call"):
        path_s = LogisticL1(opts=opts).path(design, y,
                                            path_len=SPARSE_PATH_LEN)
    check_path(path_s, X, y, "sparse slab")
    with timed("sparse: same data's dense path, first call"):
        path_d = LogisticL1(opts=opts).path(DenseDesign(X), y,
                                            path_len=SPARSE_PATH_LEN)
    check_path(path_d, X, y, "sparse dense")
    check_agree(X, y, path_s, path_d, "sparse slab vs dense")


def run_one_chip(eps_cfg, sparse_cfg, seed: int) -> None:
    """Fit, reference, serve and sparse phases on one chip."""
    opts = DGLMNETOptions(tile=TILE)
    with timed("fit: epsilon data on device"):
        X, y = make_data(eps_cfg, seed)
        X.block_until_ready()
    log(f"# fit: X {X.shape} {X.dtype}, {X.nbytes / 1e9:.2f} GB on device")
    est = path = None
    with phase("fit"):
        est, path = fit_phase(X, y, opts)
    with phase("reference"):
        if path is not None:
            reference_phase(X, y, opts, path)
    with phase("serve"):
        if path is None:
            raise RuntimeError("no fitted path to serve")
        serve_phase(est, path, X.shape[1], seed)
    del X, y, est, path
    with phase("sparse"):
        X, y = make_data(sparse_cfg, seed + 1)
        sparse_phase(X, y, opts, make_mesh((1, 1), ("data", "model")))


def run_four_chips(eps_cfg, sparse_cfg, seed: int) -> None:
    """2x2 data x model mesh paths against one chip (device 0)."""
    if len(jax.devices()) < 4:
        raise SystemExit(f"FAIL: --chips 4 needs 4 devices, found "
                         f"{len(jax.devices())}")
    mesh = make_mesh((2, 2), ("data", "model"))
    one = make_mesh((1, 1), ("data", "model"))
    all4 = {d.id for d in mesh.devices.flat}
    log(f"# mesh: 2x2 data x model on devices "
        f"{[d.id for d in mesh.devices.flat]}")
    opts = DGLMNETOptions(tile=TILE)

    with phase("dense 2x2"):
        X, y = make_data(eps_cfg, seed)
        with timed("dense: epsilon path on one chip, first call"):
            path1 = LogisticL1(opts=opts).path(DenseDesign(X), y,
                                               path_len=PATH_LEN)
        check_path(path1, X, y, "dense 1 chip")
        Xs = jax.device_put(X, NamedSharding(mesh, P("data", "model")))
        if shard_devices(Xs, "epsilon X") != all4:
            fail("epsilon X does not span the four chips")
        with timed("dense: epsilon path on the 2x2 mesh, first call"):
            path4 = LogisticL1(opts=opts).path(
                ShardedDesign(DenseDesign(Xs), mesh, tile=TILE), y,
                path_len=PATH_LEN)
        check_path(path4, X, y, "dense 2x2")
        check_agree(X, y, path4, path1, "dense 2x2 vs 1 chip", betas=True)
        del X, y, Xs, path1, path4

    with phase("sparse 2x2"):
        X, y = make_data(sparse_cfg, seed + 1)
        d1 = slab_design(X, one)
        d4 = slab_design(X, mesh)
        with timed("sparse: slab path on one chip, first call"):
            path1 = LogisticL1(opts=opts).path(d1, y,
                                               path_len=SPARSE_PATH_LEN)
        check_path(path1, X, y, "sparse 1 chip")
        with timed("sparse: slab path on the 2x2 mesh, first call"):
            path4 = LogisticL1(opts=opts).path(d4, y,
                                               path_len=SPARSE_PATH_LEN)
        check_path(path4, X, y, "sparse 2x2")
        for r_b, v_b, _ in d4._mesh_state(TILE).iter_buckets():
            if (shard_devices(r_b, f"slab bucket rows {r_b.shape}") != all4
                    or shard_devices(v_b, f"slab bucket values {v_b.shape}")
                    != all4):
                fail("slab buckets do not span the four chips")
        check_agree(X, y, path4, path1, "sparse 2x2 vs 1 chip", betas=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fit, serve and sparse phases on one chip; "
                         "4: only the 2x2 mesh paths against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"# device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if dev.platform != "tpu":
        raise SystemExit(f"FAIL: no TPU: JAX found {dev.platform}; this "
                         f"script runs only on the chip")

    cache = enable_compile_cache()
    if cache is None:
        log("# compile cache: off (JAX_ENABLE_COMPILATION_CACHE)")
    else:
        entries = (len(list(Path(cache).iterdir()))
                   if Path(cache).is_dir() else 0)
        log(f"# compile cache: {cache}, {entries} entries at start (first "
            f"calls below compile only what it does not hold)")
    if ops.interpret_default():
        raise SystemExit("FAIL: kernels would run in interpret mode on a TPU")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(GLM_EPSILON, SPARSE_TWIN, args.seed)
    else:
        run_one_chip(GLM_EPSILON, SPARSE_TWIN, args.seed)
    log(f"# time: total {time.perf_counter() - t0:.3f} s")
    if FAILURES:
        raise SystemExit(f"FAIL: {len(FAILURES)} check(s) failed:\n"
                         + "\n".join(FAILURES))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
