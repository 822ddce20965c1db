"""Fit traffic: whole KKT-certified paths, back to back, on one design.

Set-up makes the configuration's data from the seed on the device, wraps
it in the program's ``DenseDesign`` and runs one path, which
compiles every shape the window meets. The window then runs a fresh
``LogisticL1(...).path(design, y, ...)`` after another until ``seconds``
have passed; ``path_s`` is the window's length over the paths it ran.

The first and the last path of the window are kept and checked, after the
window, against the plain reference (``bench.ref.fit``) over the data the
benchmark generated; their readings are the largest over both.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax

from bench.harness import Window, checks_of


@dataclass
class State:
    cell: object
    gen: object
    data: dict
    design: object
    opts: object
    log: object
    path_len: int
    kkt_tol: float


def build_design(cell, seed: int):
    """(generator, data, program design, options) for the cell."""
    from repro.api import DenseDesign
    from repro.core.dglmnet import DGLMNETOptions

    cfg = cell.config
    if cfg["layout"] != "dense":
        raise ValueError(f"unknown layout {cfg['layout']!r}")
    gen = cell.module("gen", cfg["generator"])
    data = gen.make(cfg, seed)
    return gen, data, DenseDesign(data["X"]), DGLMNETOptions(
        tile=int(cfg["tile"]))


def run_path(state: State):
    """One certified path through the front door, waited for."""
    from repro.api import LogisticL1

    res = LogisticL1(opts=state.opts).path(
        state.design, state.data["y"], path_len=state.path_len,
        kkt_tol=state.kkt_tol)
    jax.block_until_ready(res.betas)
    return res


def sound(res, path_len: int) -> bool:
    """Every point OK, certified by at least one KKT round, none skipped."""
    return (len(res) == path_len and res.all_ok and all(
        s.get("kkt_rounds", 0) >= 1 and not s.get("skipped")
        and not s.get("degraded") for s in res.screen))


def setup(cell, seed: int, seconds: float, log) -> State:
    t = time.perf_counter()
    gen, data, design, opts = build_design(cell, seed)
    jax.block_until_ready(data["y"])
    log(f"# setup: data on device {time.perf_counter() - t:.3f} s, "
        f"{_in_use()} bytes in use")
    state = State(cell, gen, data, design, opts, log,
                  int(cell.config["path_len"]),
                  float(cell.config["kkt_tol"]))
    t = time.perf_counter()
    res = run_path(state)
    log(f"# setup: warm path {time.perf_counter() - t:.3f} s, nnz "
        f"{res.nnz.tolist()}, iters {res.n_iters.tolist()}, kkt rounds "
        f"{[s.get('kkt_rounds') for s in res.screen]}, {_in_use()} bytes "
        f"in use")
    return state


def measure(state: State, seconds: float) -> Window:
    from bench.harness import CompileCounter

    kept, n, failed, errors = [], 0, 0, []
    with CompileCounter() as compiles:
        t0 = time.perf_counter()
        while True:
            n += 1
            try:
                res = run_path(state)
            except Exception as e:  # noqa: BLE001 - a path that raises fails
                failed += 1
                errors.append(repr(e))
            else:
                failed += not sound(res, state.path_len)
                kept = [kept[0], res] if kept else [res]
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    return Window(metrics={"path_s": elapsed / n}, attempted=n,
                  failed=failed, outputs=kept,
                  notes=[f"# window: {n} paths in {elapsed!r} s, "
                         f"{compiles.count} compiles in the window"]
                  + [f"# error: {e}" for e in errors[:3]])


def traced(state: State, seconds: float, capture) -> Window:
    """One path timed with the profiler off, then one path traced, with
    the fetches through ``engine.device_get`` counted."""
    from repro.core import engine

    t0 = time.perf_counter()
    res0 = run_path(state)
    path_s = time.perf_counter() - t0
    fetches = [0]
    orig = engine.device_get

    def counted(x):
        fetches[0] += 1
        return orig(x)

    engine.device_get = counted
    try:
        with capture.window() as w:
            res1 = run_path(state)
    finally:
        engine.device_get = orig
    red = w.reduce()
    facts = {
        "path_s": path_s, "fetches": fetches[0], "spans": w.spans,
        "trace": red, "config": state.cell.config, "gen": state.gen,
        "peaks": _peaks(),
        "device": {"busy_s": red["busy_s"], "window_s": red["window_s"]},
        "breakdown": red["breakdown"],
    }
    ok = sound(res0, state.path_len) + sound(res1, state.path_len)
    return Window(metrics={}, attempted=2, failed=2 - ok,
                  outputs=[res0, res1], facts=facts,
                  notes=[f"# traced: untraced path {path_s!r} s, traced "
                         f"path {w.t1 - w.t0!r} s"])


def _in_use() -> int:
    return int((jax.devices()[0].memory_stats() or {}).get("bytes_in_use",
                                                           0))


def _peaks() -> dict:
    from bench.harness import peaks

    return peaks(jax.devices()[0].device_kind)


def readings(state: State, paths) -> dict:
    """The reference's worst readings over ``(betas, lambdas)`` pairs."""
    from bench.ref import fit as ref

    worst: dict = {}
    for betas, lambdas in paths:
        r = ref.dense_path(state.data, betas, lambdas)
        worst = {k: max(v, worst.get(k, v)) for k, v in r.items()}
    return worst


def check(state: State, win: Window) -> list:
    paths = [(r.betas, r.lambdas) for r in win.outputs]
    state.design = win.outputs = None     # the program's state goes first
    vals = readings(state, paths)
    for k, v in vals.items():
        state.log(f"# reading {k} {v!r}")
    return checks_of(state.cell, vals)



def reading(cell, seed: int, *, control: bool, seconds: float, log) -> dict:
    """One path's readings on ``seed`` (``bench/tools/limits.py``). The
    control hands the reference the path's coefficients rounded to
    bfloat16, one precision below the float32 the configuration states."""
    import jax.numpy as jnp

    gen, data, design, opts = build_design(cell, seed)
    state = State(cell, gen, data, design, opts, log,
                  int(cell.config["path_len"]),
                  float(cell.config["kkt_tol"]))
    res = run_path(state)
    betas = res.betas
    if control:
        betas = jnp.asarray(betas).astype(jnp.bfloat16).astype(jnp.float32)
    state.design = None
    return readings(state, [(betas, res.lambdas)])
