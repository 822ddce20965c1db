#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/tools/limits.py --workload epsilon.path \
        --seeds 101-112 --control-seeds 201-203

One process reads the program's sound runs on ``--seeds`` and the
control's on ``--control-seeds`` (each driver's ``reading``), and prints
one JSON line per seed and a summary: for each number, the lower reading
(the largest of the sound runs) and the upper one (the smallest of the
control's). The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window of each serve reading (default: the "
                         "benchmark's run_seconds)")
    args = ap.parse_args()

    import jax

    from bench.harness import load_cell, log

    if jax.devices()[0].platform != "tpu":
        log("FAIL: readings for limits are taken on the chip")
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(args.workload, ROOT)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or float(spec["run_seconds"])
    driver = cell.module("drive", cell.traffic["driver"])
    got = {"sound": [], "control": []}
    for kind, ss in (("sound", seeds(args.seeds)),
                     ("control", seeds(args.control_seeds))):
        for s in ss:
            t = time.perf_counter()
            r = driver.reading(cell, s, control=kind == "control",
                               seconds=seconds, log=log)
            got[kind].append(r)
            print(json.dumps({"kind": kind, "seed": s, "readings": r,
                              "s": time.perf_counter() - t}), flush=True)
    names = sorted({k for rs in got.values() for r in rs for k in r})
    summary = {}
    for k in names:
        lo = [r[k] for r in got["sound"] if k in r]
        hi = [r[k] for r in got["control"] if k in r]
        summary[k] = {"lower": max(lo) if lo else None,
                      "upper": min(hi) if hi else None,
                      "sound": lo, "control": hi}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
