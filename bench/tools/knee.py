#!/usr/bin/env python3
"""Find a serve cell's knee on the chip: the highest offered rate it
sustains.

    python3 bench/tools/knee.py --workload rcv1.docs-max --rates 500,1000 \
        --seconds 10 --seed 5 --set max_batch=64

One process offers each rate in turn through the cell's own driver (the
traffic file's rate replaced) and prints, per rate, the p95 latency, the
rate scored by the window's close and the backlog left at the close. The
knee is the highest rate whose backlog stays near zero and whose latency
does not grow through the window; a cell's traffic file then carries a
fixed multiple of it as a number. ``--set key=value`` replaces a number
of the traffic file for the sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()

    import jax
    import numpy as np

    from bench.harness import load_cell, log

    if jax.devices()[0].platform != "tpu":
        log("FAIL: the knee is measured on the chip")
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(args.workload, ROOT)
    for kv in args.set:
        k, v = kv.split("=")
        cell.traffic = dict(cell.traffic, **{k: json.loads(v)})
    driver = cell.module("drive", cell.traffic["driver"])
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        state = driver.setup(cell, args.seed, args.seconds, log)
        out = driver.run_window(state, args.seconds, 30.0)
        win = driver.summarize(state, out, args.seconds)
        lat = out["done"] - out["due"]
        n = lat.size
        half = n // 2
        print(json.dumps({
            "rate": rate, "requests": n, "set": args.set,
            "p95_ms": win.metrics["serve_p95_ms"],
            "scores_per_s": win.metrics["scores_per_s"],
            "p50_first_half_ms": float(np.median(lat[:half])) * 1e3,
            "p50_second_half_ms": float(np.median(lat[half:])) * 1e3,
            "unscored": win.failed,
            "late_max_s": float(np.max(out["late"])),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
