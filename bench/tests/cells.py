"""Tiny cells written as files in a directory, as a later PR would add
them: a ``BENCHMARK.json`` entry plus configuration, traffic and limits
files, and nothing else."""
from __future__ import annotations

import json
from pathlib import Path

TINY_CONFIGS = {
    "tinydense": {"generator": "dense", "layout": "dense",
                  "train_rows": 2048, "num_features": 64,
                  "nnz_per_example": 64, "path_len": 6, "kkt_tol": 1e-3,
                  "tile": 32},
    "tinywide": {"num_features": 4096,
                 "documents": {"tokens_mean": 40, "tokens_sigma": 1.0,
                               "tokens_min": 5, "tokens_max": 400,
                               "vocab": 20000, "value_min": 0.05,
                               "value_max": 1.0, "unit_norm": True},
                 "path": {"points": 4, "nnz_first": 50, "nnz_last": 800}},
}
TINY_TRAFFIC = {
    "tinypath": {"driver": "fit"},
    "tinydocs": {"driver": "serve", "rate_per_s": 20.0, "max_batch": 16,
                 "drain_wait_s": 20},
}
FIT_LIMITS = {"kkt_excess": {"limit": 0.01}}
SERVE_LIMITS = {"score_gap": {"limit": 1e-5}}
CELLS = {
    "tinydense.path": ("tinydense", "tinypath", FIT_LIMITS),
    "tinywide.docs": ("tinywide", "tinydocs", SERVE_LIMITS),
}


def write_cells(root: Path, real_spec: dict) -> Path:
    """A checkout-like tree under ``root`` holding only the tiny cells'
    data files and a ``BENCHMARK.json``: the real metrics, moved to the
    tiny serve cell, and a ``path_s`` for the tiny fit cells."""
    bench = root / "bench"
    for kind, table in (("configs", TINY_CONFIGS), ("traffic", TINY_TRAFFIC)):
        (bench / kind).mkdir(parents=True, exist_ok=True)
        for name, body in table.items():
            (bench / kind / f"{name}.json").write_text(json.dumps(body))
    (bench / "limits").mkdir(parents=True, exist_ok=True)
    workloads = []
    for cell, (cfg, tr, limits) in CELLS.items():
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(limits))
        workloads.append({"name": cell, "config": cfg, "traffic": tr,
                          "chips": 1, "why": "tiny CPU cell"})
    fit = [c for c in CELLS if c.endswith(".path")]
    serve = [c for c in CELLS if c.endswith(".docs")]
    path_s = {"name": "path_s", "unit": "s", "better": "lower",
              "bound": 0.2, "source": "host_clock", "workloads": fit}
    spec = dict(real_spec, workloads=workloads,
                end_to_end=[path_s] + real_spec["end_to_end"])
    for m in real_spec["end_to_end"] + real_spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = serve
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
