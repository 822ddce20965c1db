"""Serve traffic: hashed documents, offered open-loop at a fixed rate.

The configuration states the deployment: its feature width, its
documents (``documents``: distinct-token counts lognormal of a mean and
spread, clipped; token ids Zipf-popular over ``vocab`` ids; positive
values, scaled to unit L2 norm where ``unit_norm``) and its served path
(``path``: ``points`` coefficient vectors with nested supports growing
geometrically). The traffic file states the load: ``rate_per_s``,
``max_batch`` and ``drain_wait_s``.

Set-up makes from the seed the path, on the device in one call, and the
window's documents, in bulk with NumPy, with a path point drawn uniformly
for each. It loads the path into the program's ``PathStore`` and warms
every scoring shape the window can meet, through the same submit, drain
and score calls.

The window offers ``round(rate * seconds)`` requests at fixed due times:
the gaps are the quantiles of the exponential law, scaled to the window.
Lengths and gaps come in one fixed order, so every seed offers the same
work at the same times; the seed draws the documents' tokens, values and
path points. One generator thread submits each at its due time
(``RequestBatcher.submit`` encodes it); one server thread drains whenever
the queue holds a request and scores the batch (``PathScorer.score``). A
request's latency runs from its due time to its score's return; one that
is refused or never scored counts as a miss at the top.
``serve_p95_ms`` is the 95th percentile (nearest rank) over all requests
due in the window; ``scores_per_s`` the requests scored by the window's
close over its length. After the close the harness waits up to
``drain_wait_s`` for the queue to empty.
"""
from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Window, checks_of


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

# the order of document lengths and arrival gaps, the same for every seed:
# drawn per seed, bursts fell in other places and the p95 of two seeds
# differed by up to 46 % while one seed repeated within 10 %
ORDER_SEED = 20141124


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def doc_lengths(dc: dict, n: int, rng) -> np.ndarray:
    """Distinct-token counts: the ``n`` quantiles of the clipped lognormal
    of mean ``tokens_mean``, in the order ``rng`` draws."""
    from statistics import NormalDist

    sigma = float(dc["tokens_sigma"])
    mu = math.log(float(dc["tokens_mean"])) - sigma * sigma / 2
    dist = NormalDist(mu, sigma)
    q = np.array([dist.inv_cdf((i + 0.5) / n) for i in range(n)])
    lens = np.clip(np.round(np.exp(q)), dc["tokens_min"], dc["tokens_max"])
    return rng.permutation(lens.astype(np.int64))


def doc_tokens(lengths: np.ndarray, vocab: int, rng) -> np.ndarray:
    """``lengths[d]`` distinct token ids for each document ``d``, as keys
    ``d * vocab + id`` sorted by document. Ids are Zipf(1)-popular over
    ``[0, vocab)``: rank ``r`` is drawn with probability ``log((r + 2) /
    (r + 1)) / log(vocab + 1)``. Repeats within a document are dropped
    and drawn again until each has its count."""
    docs = np.arange(lengths.size)
    keys = np.zeros(0, np.int64)
    need = lengths
    while need.any():
        d = np.repeat(docs, need)
        ids = np.floor(np.exp(rng.random(d.size) * math.log(vocab + 1)))
        keys = np.union1d(keys, d * vocab + ids.astype(np.int64) - 1)
        need = lengths - np.bincount(keys // vocab, minlength=lengths.size)
    return keys


def make_docs(dc: dict, n: int, seed: int) -> list:
    """``n`` requests: ``{token: value}`` maps of positive values, scaled
    to unit L2 norm per document where ``unit_norm``. The lengths come in
    one order for every seed; the seed draws the tokens and values."""
    rng = rng_of(seed, 1)
    vocab = int(dc["vocab"])
    lengths = doc_lengths(dc, n, rng_of(ORDER_SEED, 1))
    keys = doc_tokens(lengths, vocab, rng)
    vals = rng.uniform(dc["value_min"], dc["value_max"], keys.size)
    doc = keys // vocab
    if dc.get("unit_norm"):
        vals /= np.sqrt(np.bincount(doc, vals * vals, n))[doc]
    names = np.array([f"t{i}" for i in range(vocab)], dtype=object)
    tokens = names[keys % vocab].tolist()
    vals = vals.tolist()
    ends = np.cumsum(lengths).tolist()
    return [dict(zip(tokens[a:b], vals[a:b]))
            for a, b in zip([0] + ends[:-1], ends)]


def due_times(n: int, seconds: float) -> np.ndarray:
    """Due offsets in ``[0, seconds)``: the ``n + 1`` quantiles of the
    exponential law as gaps, in one shuffled order for every seed, scaled
    to span the window."""
    gaps = -np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1))
    gaps = rng_of(ORDER_SEED, 2).permutation(gaps)
    return np.cumsum(gaps)[:n] * seconds / gaps.sum()


@partial(jax.jit, static_argnames=("p", "points", "nnz_first",
                                   "nnz_last"))
def _path(key, *, p: int, points: int, nnz_first: int, nnz_last: int):
    """Stacked ``(points, p)`` coefficients with nested supports."""
    kp, kv = jax.random.split(key)
    support = jax.random.permutation(kp, p)[:nnz_last]
    vals = jax.random.normal(kv, (nnz_last,), jnp.float32)
    ratio = (nnz_last / nnz_first) ** (1.0 / max(points - 1, 1))
    nnz = jnp.round(nnz_first * ratio ** jnp.arange(points)).astype(jnp.int32)
    live = jnp.arange(nnz_last)[None, :] < nnz[:, None]
    return jnp.zeros((points, p), jnp.float32).at[:, support].set(
        jnp.where(live, vals[None, :], 0.0))


def make_path(pt: dict, p: int, seed: int):
    """(lambdas descending, device betas) of the served path."""
    from bench.gen.dense import key_of

    betas = _path(key_of(seed), p=p, points=int(pt["points"]),
                  nnz_first=int(pt["nnz_first"]),
                  nnz_last=int(pt["nnz_last"]))
    lams = 2.0 ** -np.arange(1, int(pt["points"]) + 1, dtype=np.float64)
    return lams, betas


# ---------------------------------------------------------------------------
# set-up and window
# ---------------------------------------------------------------------------

@dataclass
class State:
    cell: object
    log: object
    p: int
    lams: np.ndarray
    betas: object
    docs: list
    lam_idx: np.ndarray
    due: np.ndarray
    store: object = None
    scorer: object = None
    batcher: object = None


def new_batcher(state: State):
    from repro.serve import RequestBatcher

    return RequestBatcher(state.p, max_batch=int(state.cell.traffic[
        "max_batch"]), max_pending=len(state.docs) + 1)


def setup(cell, seed: int, seconds: float, log) -> State:
    from repro.api.types import PathResult
    from repro.serve import PathScorer, PathStore

    cfg = cell.config
    p = int(cfg["num_features"])
    n = int(round(float(cell.traffic["rate_per_s"]) * seconds))
    t = time.perf_counter()
    lams, betas = make_path(cfg["path"], p, seed)
    L = len(lams)
    result = PathResult(
        lambdas=lams, betas=betas, nnz=np.zeros(L, np.int64),
        f=np.zeros(L), n_iters=np.zeros(L, np.int64),
        status=np.zeros(L, np.int64))
    store = PathStore(result)
    docs = make_docs(cfg["documents"], n, seed)
    lam_idx = rng_of(seed, 3).integers(0, L, n)
    state = State(cell, log, p, lams, betas, docs, lam_idx,
                  due_times(n, seconds), store=store,
                  scorer=PathScorer(store))
    log(f"# setup: path and {n} documents in {time.perf_counter() - t:.3f}"
        f" s, mean tokens {np.mean([len(d) for d in docs]):.1f}")
    t = time.perf_counter()
    warm(state)
    log(f"# setup: warm scoring shapes {time.perf_counter() - t:.3f} s")
    state.batcher = new_batcher(state)
    return state


def warm(state: State) -> None:
    """Score every (batch capacity, K class) the window can meet. A batch
    of capacity ``c`` holds one request (the least class) or ``c // 2 +
    1``; ``k`` copies of one request among them set its K class, distinct
    tokens the least one."""
    from repro.serve.batcher import batch_capacity
    from repro.serve.ingest import k_capacity

    b = new_batcher(state)
    mb = int(state.cell.traffic["max_batch"])
    caps = sorted({batch_capacity(m, b_max=mb) for m in range(1, mb + 1)})
    for cap in caps:
        m = 1 if cap == caps[0] else cap // 2 + 1
        for k in sorted({k_capacity(j) for j in range(1, m + 1)}):
            share = k if k > k_capacity(1) else 1
            reqs = [state.docs[0]] * min(share, m) + [
                {f"warm{i}_{j}": 1.0 for j in range(8)}
                for i in range(m - min(share, m))]
            for r in reqs:
                b.submit(r, float(state.lams[0]))
            batch, lams = b.drain()
            state.scorer.score(batch, lams)


def run_window(state: State, seconds: float, wait_s: float) -> dict:
    """Offer the schedule; returns per-request done times and scores."""
    n = len(state.docs)
    done = np.full(n, np.nan)
    scores = np.full(n, np.nan)
    late = np.zeros(n)
    refused = np.zeros(n, bool)
    queued: list = []
    lock = threading.Lock()
    wake = threading.Event()
    gen_over = threading.Event()
    stop = threading.Event()
    errors: list = []
    batcher, scorer = state.batcher, state.scorer
    t_start = time.perf_counter() + 0.05
    due = t_start + state.due

    def generate():
        for i in range(n):
            wait = due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - due[i]
            with lock:
                queued.append(i)
            try:
                batcher.submit(state.docs[i],
                               float(state.lams[state.lam_idx[i]]))
            except Exception as e:  # noqa: BLE001 - a refusal is a miss
                with lock:
                    queued.remove(i)
                refused[i] = True
                errors.append(repr(e))
            wake.set()
        gen_over.set()

    def serve():
        try:
            while not stop.is_set():
                if len(batcher) == 0:
                    if gen_over.is_set() and not queued:
                        return
                    wake.wait(0.002)
                    wake.clear()
                    continue
                batch, lams = batcher.drain()
                if batch.n_live == 0:
                    continue
                s, _ = scorer.score(batch, lams)
                t = time.perf_counter()
                batcher.mark_scored()
                with lock:
                    ids, queued[:batch.n_live] = queued[:batch.n_live], []
                scores[ids] = np.asarray(s, np.float64)[:len(ids)]
                done[ids] = t
        except Exception as e:  # noqa: BLE001 - recorded; unscored = miss
            errors.append(repr(e))

    threads = [threading.Thread(target=generate, daemon=True),
               threading.Thread(target=serve, daemon=True)]
    for th in threads:
        th.start()
    threads[0].join(timeout=seconds + wait_s + 5)
    threads[1].join(timeout=max(t_start + seconds + wait_s
                                - time.perf_counter(), 0.0))
    stop.set()
    for th in threads:
        th.join(timeout=30)
    return {"t_start": t_start, "due": due, "done": done, "scores": scores,
            "late": late, "refused": refused, "errors": errors,
            "alive": any(th.is_alive() for th in threads)}


def summarize(state: State, out: dict, seconds: float) -> Window:
    lat = out["done"] - out["due"]
    lat = np.where(np.isfinite(lat), lat, np.inf)
    n = lat.size
    rank = max(int(math.ceil(0.95 * n)) - 1, 0)
    p95 = float(np.sort(lat)[rank]) * 1e3 if n else math.inf
    in_window = int(np.sum(out["done"] <= out["t_start"] + seconds))
    scored = np.isfinite(out["done"])
    notes = [f"# generator lateness: max {float(np.max(out['late'])):.6f} s,"
             f" mean {float(np.mean(out['late'])):.6f} s over {n} requests",
             f"# window: {int(scored.sum())} of {n} scored, "
             f"{in_window} by the close; refused {int(out['refused'].sum())}"]
    notes += [f"# error: {e}" for e in out["errors"][:5]]
    return Window(metrics={"serve_p95_ms": p95,
                           "scores_per_s": in_window / seconds},
                  attempted=n, failed=int(n - scored.sum()),
                  outputs=out, notes=notes)


def measure(state: State, seconds: float) -> Window:
    from bench.harness import CompileCounter, GcPauses

    with CompileCounter() as compiles, GcPauses() as pauses:
        out = run_window(state, seconds,
                         float(state.cell.traffic["drain_wait_s"]))
    win = summarize(state, out, seconds)
    win.notes += [f"# {compiles.count} compiles in the window",
                  pauses.note()]
    return win


def traced(state: State, seconds: float, capture) -> Window:
    with capture.window() as w:
        out = run_window(state, seconds,
                         float(state.cell.traffic["drain_wait_s"]))
    win = summarize(state, out, seconds)
    red = w.reduce()
    win.facts = {"spans": w.spans, "trace": red,
                 "serve_p95_ms": win.metrics["serve_p95_ms"],
                 "device": {"busy_s": red["busy_s"],
                            "window_s": red["window_s"]},
                 "breakdown": red["breakdown"]}
    return win


def readings(state: State, out: dict, *, round_bf16: bool = False) -> dict:
    """Widest gap of a served score from the plain reference's."""
    from bench.ref import score as ref

    betas = np.asarray(jax.device_get(state.betas))
    idx = np.flatnonzero(np.isfinite(out["done"]))
    gap = ref.widest_gap(
        [state.docs[i] for i in idx], state.lam_idx[idx], out["scores"][idx],
        betas, round_bf16=round_bf16)
    return {"score_gap": gap}


def check(state: State, win: Window) -> list:
    out = win.outputs
    state.store = state.scorer = state.batcher = None
    vals = readings(state, out)
    for k, v in vals.items():
        state.log(f"# reading {k} {v!r}")
    return checks_of(state.cell, vals)


def reading(cell, seed: int, *, control: bool, seconds: float, log) -> dict:
    """One window's readings on ``seed`` at the cell's load
    (``bench/tools/limits.py``); the control scores the same requests with
    the reference in bfloat16."""
    state = setup(cell, seed, seconds, log)
    out = run_window(state, seconds, float(cell.traffic["drain_wait_s"]))
    state.store = state.scorer = state.batcher = None
    return readings(state, out, round_bf16=control)
