"""Serving layer + PathResult API: typed path results round-trip through
checkpoints, hashed ingestion is deterministic, and batched path scoring
is bit-identical to ``LogisticL1.decision_function`` — locally and (slow
lane, subprocess fake devices) on a 2x4 mesh. Hot-swap must never mix two
path versions inside one batch."""
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import LogisticL1, PathPoint, PathResult, SlabDesign
from repro.serve import (
    PathScorer,
    PathStore,
    RequestBatcher,
    batch_capacity,
    encode_request,
    hash_token,
    k_capacity,
    pack_requests,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def _problem(seed=0, n=64, p=24, density=0.2):
    rng = np.random.default_rng(seed)
    X = ((rng.random((n, p)) < density)
         * rng.normal(size=(n, p))).astype(np.float32)
    y = np.where(rng.random(n) < 0.5, 1.0, -1.0).astype(np.float32)
    return X, y


def _tokens_for(p):
    """One token per column that hashes exactly to that column."""
    toks = {}
    for j in range(p):
        t = 0
        while hash_token(f"tok{j}_{t}", p) != j:
            t += 1
        toks[j] = f"tok{j}_{t}"
    return toks


def _requests_from_rows(X, toks):
    return [{toks[j]: float(X[i, j]) for j in range(X.shape[1])
             if X[i, j] != 0.0} for i in range(X.shape[0])]


@pytest.fixture(scope="module")
def fitted():
    X, y = _problem()
    est = LogisticL1()
    path = est.path(X, y, path_len=5)
    return X, y, est, path


# ---------------------------------------------------------------------------
# PathResult typed API + back-compat
# ---------------------------------------------------------------------------

def test_pathresult_type_and_backcompat(fitted):
    _, _, _, path = fitted
    assert isinstance(path, PathResult)
    assert len(path) == 5
    assert path.betas.shape == (5, 24)
    assert path.lambdas.shape == (5,)
    # descending geometric grid
    assert np.all(np.diff(path.lambdas) < 0)
    # list-of-PathPoint protocol the pre-PathResult call sites used
    pts = list(path)
    assert len(pts) == 5 and all(isinstance(q, PathPoint) for q in pts)
    assert isinstance(path[0], PathPoint)
    assert path[-1].lam == pts[-1].lam          # negative indexing
    assert [q.lam for q in path[1:3]] == [pts[1].lam, pts[2].lam]
    with pytest.raises(IndexError):
        path[5]
    # stacked rows == per-point betas, per-point scalars == stacked arrays
    for i, q in enumerate(pts):
        assert np.array_equal(np.asarray(path.betas[i]), np.asarray(q.beta))
        assert path.nnz[i] == q.nnz
        assert path.lambdas[i] == q.lam


def test_pathresult_index_of(fitted):
    _, _, _, path = fitted
    for i, lam in enumerate(path.lambdas):
        assert path.index_of(float(lam)) == i
        # log-nearest: a point 10% off still resolves to the same index
        assert path.index_of(float(lam) * 1.1) == i
    assert path.index_of(0.0) == len(path) - 1      # clamps, no -inf blowup


def test_pathresult_save_load_roundtrip(fitted, tmp_path):
    _, _, _, path = fitted
    d = str(tmp_path / "ckpt")
    path.save(d)
    loaded = PathResult.load(d)
    assert np.array_equal(np.asarray(loaded.betas), np.asarray(path.betas))
    assert np.array_equal(loaded.lambdas, path.lambdas)
    assert np.array_equal(loaded.nnz, path.nnz)
    assert np.array_equal(loaded.f, path.f)
    assert np.array_equal(loaded.n_iters, path.n_iters)
    assert len(loaded.metrics) == len(path.metrics)
    assert len(loaded.screen) == len(path.screen)
    # screen telemetry survives the JSON manifest with its values intact
    for a, b in zip(loaded.screen, path.screen):
        assert set(a) == set(b)
        for k in a:
            assert np.isclose(float(a[k]), float(b[k]))


def test_pathstore_from_checkpoint_serves(fitted, tmp_path):
    X, _, _, path = fitted
    d = str(tmp_path / "ckpt")
    path.save(d)
    store = PathStore.from_checkpoint(d)
    assert store.snapshot.p == X.shape[1]
    assert store.version == 1


# ---------------------------------------------------------------------------
# sklearn surface
# ---------------------------------------------------------------------------

def test_sklearn_surface(fitted):
    X, y, est, path = fitted
    # allow[nonfinite-guard]: sklearn-surface oracle on a healthy fit, not served output; sign test below would fail on NaN anyway
    scores = np.asarray(est.decision_function(X))
    pred = np.asarray(est.predict(X))
    assert set(np.unique(pred)) <= {-1.0, 1.0}
    assert np.array_equal(pred, np.where(scores >= 0.0, 1.0, -1.0))
    assert np.array_equal(np.asarray(est.coef_), np.asarray(est.beta_))
    assert est.intercept_ == 0.0                 # paper model has no bias
    params = est.get_params()
    assert set(params) == {"opts", "mesh", "warm_start"}
    est2 = LogisticL1(**params)
    assert est2.get_params() == params
    est2.set_params(warm_start=False)
    assert est2.warm_start is False
    with pytest.raises(ValueError):
        est2.set_params(no_such_param=1)


# ---------------------------------------------------------------------------
# hashed ingestion
# ---------------------------------------------------------------------------

def test_hashing_deterministic_and_order_free():
    p = 97
    # CRC32 is process-stable: pin a few values so a hash change is loud
    assert hash_token("hello", p) == (0x3610A686 % p)
    i1, v1 = encode_request({"a": 1.0, "b": 2.0, "c": 3.0}, p)
    i2, v2 = encode_request([("c", 3.0), ("a", 1.0), ("b", 2.0)], p)
    assert np.array_equal(i1, i2) and np.array_equal(v1, v2)


def test_hash_collisions_sum_in_sorted_token_order():
    # find two tokens that collide at small p
    p = 3
    toks = ["t%d" % i for i in range(50)]
    by_idx = {}
    for t in toks:
        by_idx.setdefault(hash_token(t, p), []).append(t)
    idx, pair = next((j, ts) for j, ts in by_idx.items() if len(ts) >= 2)
    a, b = pair[0], pair[1]
    i1, v1 = encode_request({a: 0.25, b: 0.5}, p)
    i2, v2 = encode_request({b: 0.5, a: 0.25}, p)
    assert np.array_equal(i1, i2) and np.array_equal(v1, v2)
    assert idx in i1
    assert v1[list(i1).index(idx)] == np.float32(0.75)


def test_empty_and_all_zero_requests():
    p = 16
    ei, ev = encode_request({}, p)
    zi, zv = encode_request({"x": 0.0, "y": 0.0}, p)
    assert ei.size == 0 and zi.size == 0
    # cancelling collision -> dropped slot too
    pcol = 3
    by_idx = {}
    for t in ["t%d" % i for i in range(50)]:
        by_idx.setdefault(hash_token(t, pcol), []).append(t)
    a, b = next(ts for ts in by_idx.values() if len(ts) >= 2)[:2]
    ci, _ = encode_request({a: 1.0, b: -1.0}, pcol)
    assert ci.size == 0
    batch = pack_requests([(ei, ev), (zi, zv)], p)
    assert batch.n_live == 2
    assert np.all(batch.row_idx == batch.n_loc)      # all-sentinel slabs
    scores, _ = PathScorer(PathStore(_tiny_path(p))).score(
        batch, np.ones(2))
    assert np.array_equal(scores, np.zeros(2, np.float32))


def _tiny_path(p):
    return PathResult(
        lambdas=np.asarray([1.0, 0.5]),
        betas=jnp.asarray(np.random.default_rng(3).normal(size=(2, p)),
                          jnp.float32),
        nnz=np.asarray([p, p]), f=np.zeros(2), n_iters=np.ones(2, np.int64),
        metrics=[{}, {}], screen=[{}, {}])


def test_capacity_classes():
    assert k_capacity(0) == 8 and k_capacity(8) == 8 and k_capacity(9) == 16
    assert batch_capacity(1) == 8
    assert batch_capacity(65) == 128
    assert batch_capacity(10_000, b_max=256) == 256


def test_pack_requests_front_packed_and_bounded():
    p = 8
    rng = np.random.default_rng(7)
    encoded = []
    for _ in range(10):
        k = rng.integers(0, 5)
        idx = np.sort(rng.choice(p, size=k, replace=False)).astype(np.int64)
        encoded.append((idx, rng.normal(size=k).astype(np.float32)))
    batch = pack_requests(encoded, p, dp=2)
    assert batch.dp == 2 and batch.batch_cap % 2 == 0
    live = batch.row_idx < batch.n_loc
    # front-packed: live slots precede sentinels in every (feature, shard)
    runs = live.cumsum(axis=-1)
    assert np.all(live[..., 1:] <= live[..., :-1])
    # every nonzero lands where its request row put it
    total = sum(len(i) for i, _ in encoded)
    assert int(live.sum()) == total
    assert int(runs[..., -1].max()) <= batch.row_idx.shape[2]


# ---------------------------------------------------------------------------
# served scores == decision_function (the acceptance bit)
# ---------------------------------------------------------------------------

def test_served_scores_bit_equal_decision_function(fitted):
    X, _, est, path = fitted
    n, p = X.shape
    toks = _tokens_for(p)
    reqs = _requests_from_rows(X, toks)
    store = PathStore(path)
    scorer = PathScorer(store)
    batcher = RequestBatcher(p, max_batch=128)
    for i, r in enumerate(reqs):
        batcher.submit(r, float(path.lambdas[i % len(path)]))
    batch, lams = batcher.drain()
    assert batch.n_live == n
    design = SlabDesign(jnp.asarray(batch.row_idx),
                        jnp.asarray(batch.values), batch.batch_cap)
    for l in range(len(path)):
        got, ver = scorer.score(batch, np.full(n, path.lambdas[l]))
        # allow[nonfinite-guard]: decision_function is the reference oracle; the served side of the bit-equality IS the guarded path
        ref = np.asarray(
            est.decision_function(design, beta=path.betas[l]))[:n]
        assert np.array_equal(got, ref), f"lambda index {l}"
        assert ver == store.version
    # mixed-lambda batch: each row equals its row in the uniform run
    mixed, _ = scorer.score(batch, lams)
    for l in range(len(path)):
        uni, _ = scorer.score(batch, np.full(n, path.lambdas[l]))
        rows = [i for i in range(n) if i % len(path) == l]
        assert np.array_equal(mixed[rows], uni[rows])


# ---------------------------------------------------------------------------
# entry-list scoring: O(nnz) operands, padding inert, one class per capacity
# ---------------------------------------------------------------------------

def _ref_scores(docs, lam_rows, betas):
    """Independent float64 re-score: CRC-32 re-hash of every token,
    colliding values summed; returns (scores, sum |beta v|) per doc."""
    import zlib

    p = betas.shape[1]
    ref, scale = [], []
    for doc, l in zip(docs, lam_rows):
        acc = {}
        for tok, v in doc.items():
            j = zlib.crc32(tok.encode("utf-8")) % p
            acc[j] = acc.get(j, 0.0) + float(v)
        idx = np.fromiter(acc, np.int64, len(acc))
        val = np.fromiter(acc.values(), np.float64, len(acc))
        coef = betas[l, idx].astype(np.float64)
        ref.append(float(coef @ val))
        scale.append(float(np.abs(coef) @ np.abs(val)))
    return np.asarray(ref), np.asarray(scale)


@pytest.mark.parametrize("n_req, cap", [(5, 8), (13, 16)])
def test_entry_scores_match_float64_rescore(n_req, cap):
    p, L = 4099, 4
    rng = np.random.default_rng(n_req)
    betas = rng.normal(size=(L, p)).astype(np.float32)
    betas[:, rng.random(p) < 0.5] = 0.0
    path = PathResult(
        lambdas=2.0 ** -np.arange(1, L + 1), betas=jnp.asarray(betas),
        nnz=np.zeros(L, np.int64), f=np.zeros(L), n_iters=np.ones(L, np.int64),
        metrics=[{}] * L, screen=[{}] * L)
    docs = [{f"w{rng.integers(0, 50_000)}": float(rng.uniform(0.05, 1.0))
             for _ in range(rng.integers(1, 120))} for _ in range(n_req)]
    lam_rows = rng.integers(0, L, n_req)
    batcher = RequestBatcher(p, max_batch=16)
    for d, l in zip(docs, lam_rows):
        batcher.submit(d, float(path.lambdas[l]))
    batch, lams = batcher.drain()
    assert batch.batch_cap == cap and batch.n_live == n_req
    got, _ = PathScorer(PathStore(path)).score(batch, lams)
    ref, scale = _ref_scores(docs, lam_rows, betas)
    gap = np.abs(got.astype(np.float64) - ref)
    assert np.all(gap <= 1e-5 * scale), np.max(gap / np.maximum(scale, 1e-30))


def test_entry_padding_scores_exactly_zero_under_adversarial_values():
    from dataclasses import replace

    from repro.kernels import ops as kops

    p = 64
    path = _tiny_path(p)
    batch = pack_requests([encode_request({"a": 1.0, "b": -2.0}, p),
                           encode_request({"c": 0.5}, p)], p, batch_cap=8)
    n = batch.n_entries
    clean, _ = PathScorer(PathStore(path)).score(batch, np.ones(2))
    for bad in (1e30, np.nan, -np.inf):
        val = batch.entry_val.copy()
        val[n:] = bad
        feat = batch.entry_feat.copy()
        feat[n:] = p - 1                      # a live coefficient
        poisoned = replace(batch, entry_val=val, entry_feat=feat)
        got, _ = PathScorer(PathStore(path)).score(poisoned, np.ones(2))
        assert np.array_equal(got, clean), bad
        # the padding rows of the full (batch_cap,) output are exact zeros
        full = np.asarray(kops.entry_path_spmv(
            jnp.asarray(poisoned.entry_row), jnp.asarray(feat),
            jnp.asarray(val), jnp.zeros(8, jnp.int32), path.betas))
        assert np.all(full[2:] == 0.0) and np.all(np.isfinite(full))


def test_empty_and_all_zero_requests_score_zero_through_entries():
    p = 16
    batch = pack_requests([encode_request({}, p),
                           encode_request({"x": 0.0, "y": 0.0}, p),
                           encode_request({}, p)], p, batch_cap=8)
    assert batch.n_entries == 0
    assert np.all(batch.entry_row == batch.batch_cap)
    assert not np.any(batch.entry_val)
    scores, _ = PathScorer(PathStore(_tiny_path(p))).score(batch, np.ones(3))
    assert np.array_equal(scores, np.zeros(3, np.float32))
    assert "_slabs" not in batch.__dict__     # local scoring built no slab


def test_pack_entry_bytes_do_not_grow_with_width():
    rng = np.random.default_rng(5)
    encoded = [encode_request({f"t{rng.integers(0, 10**6)}": 1.0
                               for _ in range(rng.integers(1, 200))}, 47_236)
               for _ in range(16)]

    def entry_bytes(batch):
        return (batch.entry_row.nbytes + batch.entry_feat.nbytes
                + batch.entry_val.nbytes)

    narrow = pack_requests(encoded, 47_236, batch_cap=16)
    wide = pack_requests(encoded, 16_609_143, batch_cap=16)
    assert entry_bytes(narrow) == entry_bytes(wide) == 4096 * 12
    for a in ("entry_row", "entry_feat", "entry_val"):
        assert np.array_equal(getattr(narrow, a), getattr(wide, a))
    assert "_slabs" not in wide.__dict__


def test_rcv1_like_window_meets_one_entry_class_per_capacity():
    from repro.serve.ingest import ENTRY_FLOOR_PER_ROW, entry_capacity

    p, sigma, mean = 47_236, 0.7, 74
    rng = np.random.default_rng(11)
    lens = np.clip(np.round(rng.lognormal(np.log(mean) - sigma ** 2 / 2,
                                          sigma, 20_000)), 5, 1200)
    classes, at = {}, 0
    while at < lens.size:
        b = int(rng.integers(1, 17))
        chunk = lens[at:at + b].astype(np.int64)
        at += b
        encoded = [(np.sort(rng.choice(p, k, replace=False)),
                    np.ones(k, np.float32)) for k in chunk]
        cap = batch_capacity(len(encoded), b_max=16)
        batch = pack_requests(encoded, p, batch_cap=cap)
        assert batch.n_entries == int(chunk.sum())
        classes.setdefault(cap, set()).add(batch.entry_row.shape[0])
    assert classes == {8: {ENTRY_FLOOR_PER_ROW * 8},
                       16: {ENTRY_FLOOR_PER_ROW * 16}}
    assert entry_capacity(ENTRY_FLOOR_PER_ROW * 16 + 1, 16) == (
        2 * ENTRY_FLOOR_PER_ROW * 16)


def test_scorer_validates_geometry(fitted):
    X, _, _, path = fitted
    p = X.shape[1]
    scorer = PathScorer(PathStore(path))
    batch = pack_requests([encode_request({"a": 1.0}, p)], p)
    with pytest.raises(ValueError):
        scorer.score(batch, np.ones(2))          # lam count != n_live
    wrong = pack_requests([encode_request({"a": 1.0}, p + 1)], p + 1)
    with pytest.raises(ValueError):
        scorer.score(wrong, np.ones(1))          # hashed to the wrong p


def test_hot_swap_never_mixes_versions(fitted):
    """Concurrent swaps during a scoring loop: every batch's scores must
    equal ONE version's reference scores end-to-end — never a blend."""
    X, _, _, path = fitted
    n, p = X.shape
    toks = _tokens_for(p)
    batch = pack_requests(
        [encode_request(r, p) for r in _requests_from_rows(X, toks)], p)
    lams = np.full(n, float(path.lambdas[-1]))

    flip = PathResult(
        lambdas=path.lambdas, betas=-path.betas, nnz=path.nnz, f=path.f,
        n_iters=path.n_iters, metrics=path.metrics, screen=path.screen)
    store = PathStore(path)
    scorer = PathScorer(store)
    ref = {1: scorer.score(batch, lams)[0]}
    store.swap(flip)
    ref[2] = scorer.score(batch, lams)[0]
    assert not np.array_equal(ref[1], ref[2])
    versions = [path, flip]

    stop = threading.Event()

    def swapper():
        i = 0
        while not stop.is_set():
            store.swap(versions[i % 2])
            i += 1

    t = threading.Thread(target=swapper)
    t.start()
    try:
        for _ in range(40):
            got, ver = scorer.score(batch, lams)
            want = ref[1] if ver % 2 == 1 else ref[2]
            assert np.array_equal(got, want), (
                "batch blended two coefficient versions")
    finally:
        stop.set()
        t.join()


def test_swap_releases_old_coefficients(fitted):
    """Regression for the module-lifetime path-margins cache: the store
    deliberately pins ONE retired snapshot (the last-good quarantine
    fallback), so after two swaps the twice-retired snapshot and its
    device coefficient stack must be collectible — nothing (jit dispatch
    caches included) may pin them beyond that single-slot budget.
    Numpy-backed PathResults make the store own distinct device arrays,
    so the weakrefs below watch store-owned memory, not test locals."""
    import gc
    import weakref

    X, _, _, path = fitted
    p = X.shape[1]

    def np_version(sign):
        return PathResult(
            lambdas=path.lambdas, betas=np.asarray(sign * path.betas),
            nnz=path.nnz, f=path.f, n_iters=path.n_iters,
            metrics=path.metrics, screen=path.screen)

    store = PathStore(np_version(1.0))
    scorer = PathScorer(store)
    batch = pack_requests([encode_request({"a": 1.0}, p)], p)
    lams = np.full(1, float(path.lambdas[0]))
    scorer.score(batch, lams)

    s0 = store.snapshot
    refs = weakref.ref(s0), weakref.ref(s0.betas)
    store.swap(np_version(-1.0))
    gc.collect()
    assert refs[0]() is not None, "last-good snapshot dropped too early"
    store.swap(np_version(0.5))   # v1 falls off the one-deep prev slot
    scorer.score(batch, lams)     # rebinds the dispatch's last-call caches
    del s0
    gc.collect()
    assert refs[0]() is None, "retired StoreSnapshot still pinned"
    assert refs[1]() is None, "retired coefficient stack still on device"


# ---------------------------------------------------------------------------
# mesh lane (subprocess fake devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_mesh_bit_identity_and_sharded_roundtrip(tmp_path):
    """2x4 mesh: P(model)-sharded store scores bit-equal to the sharded
    decision_function; a checkpoint loaded with an explicit sharding
    serves identically."""
    d = str(tmp_path / "ckpt")
    r = _run(f"""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.api import (LogisticL1, PathResult, ShardedDesign,
                               SlabDesign)
        from repro.launch.mesh import make_dev_mesh
        from repro.serve import (PathScorer, PathStore, RequestBatcher,
                                 hash_token)

        mesh = make_dev_mesh(2, 4)
        rng = np.random.default_rng(1)
        n, p, tile = 64, 24, 8
        X = ((rng.random((n, p)) < 0.25)
             * rng.normal(size=(n, p))).astype(np.float32)
        y = np.where(rng.random(n) < 0.5, 1.0, -1.0).astype(np.float32)
        est = LogisticL1(mesh=mesh)
        path = est.path(X, y, path_len=4)
        path.save({d!r})

        store = PathStore(path, mesh=mesh, tile=tile)
        scorer = PathScorer(store)
        toks = {{}}
        for j in range(p):
            t = 0
            while hash_token(f't{{j}}_{{t}}', p) != j:
                t += 1
            toks[j] = f't{{j}}_{{t}}'
        b = RequestBatcher(p, max_batch=128, dp=2,
                           pad_p_to=store.pad_p_to)
        for i in range(n):
            b.submit({{toks[j]: float(X[i, j]) for j in range(p)
                      if X[i, j] != 0.0}},
                     float(path.lambdas[i % len(path)]))
        batch, lams = b.drain()
        assert batch.n_live == n and batch.dp == 2

        inner = SlabDesign(jnp.asarray(batch.row_idx),
                           jnp.asarray(batch.values), batch.batch_cap)
        sd = ShardedDesign(inner, mesh, tile=tile)
        for l in range(len(path)):
            beta = jnp.pad(path.betas[l], (0, batch.p_pad - p))
            ref = np.asarray(est.decision_function(sd, beta=beta))[:n]
            got, _ = scorer.score(batch, np.full(n, path.lambdas[l]))
            assert np.array_equal(got, ref), f'lambda {{l}}'

        # sharded checkpoint load: betas land P(None, model) and serve
        # bit-identically to the local store
        sharding = NamedSharding(mesh, P(None, 'model'))
        loaded = PathResult.load({d!r}, sharding=sharding)
        assert np.array_equal(np.asarray(loaded.betas),
                              np.asarray(path.betas))
        store2 = PathStore(loaded, mesh=mesh, tile=tile)
        s2 = PathScorer(store2)
        got1, _ = scorer.score(batch, lams)
        got2, _ = s2.score(batch, lams)
        assert np.array_equal(got1, got2)
        print('MESH-SERVE-OK')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH-SERVE-OK" in r.stdout
