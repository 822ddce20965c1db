#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload epsilon.path --seed 7 --seconds 30 --trace 0

One process holds the chip. It refuses, before any work and without a
result line, a platform other than ``tpu`` or fewer chips than the cell
asks for. The compile cache lives at the fixed path ``.jax_cache`` in the
checkout (or where ``JAX_COMPILATION_CACHE_DIR`` points). Set-up makes the
data from ``--seed`` and warms every shape the window uses; the window
lasts ``--seconds``; the plain reference then decides ``correct``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a traced window. The last
line of standard output is the JSON result; the last lines of standard
error are the numbers compared, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _finite(obj):
    """JSON-safe copy: non-finite numbers become None."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench.harness import load_cell, log, run_cell

    cell = load_cell(args.workload, ROOT)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"FAIL: no TPU: JAX found {devices[0].platform}; the benchmark "
            f"runs only on the chip")
        return 2
    if len(devices) < cell.chips:
        log(f"FAIL: {cell.name} needs {cell.chips} chips, found "
            f"{len(devices)}")
        return 2
    used = devices[:cell.chips]
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"# device: platform={used[0].platform} kind={used[0].device_kind} "
        f"count={len(used)}")

    def device_info() -> dict:
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)
        return {"platform": used[0].platform, "kind": used[0].device_kind,
                "count": len(used), "memory_peak_bytes": peak}

    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START,
                      device_info=device_info)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
