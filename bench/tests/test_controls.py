"""The correctness controls, at a size a test run holds, fail the limits
the cells hold the program to, while sound runs pass them.

* Fit: the reference is handed the path's coefficients rounded to
  bfloat16, one precision below the float32 the configuration states
  (``drive/fit.reading(control=True)``), on the epsilon configuration cut
  to 20,000 x 256 on the CPU.
* Serve: the reference scores the served documents in bfloat16 in the
  program's place (``drive/serve.readings(round_bf16=True)``), on a
  4,096-wide tiny cell.

On the chip the same controls run at each cell's own size through
``bench/tools/limits.py``; ``PERF.md`` lists those readings.
"""
import json

import pytest

from bench.harness import HERE, ROOT, load_cell
from bench.tests.cells import write_cells


def _limit(cell: str, name: str) -> float:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())[
        name]["limit"]


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_fit_control_fails_where_sound_passes(tmp_path, seed):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = load_cell("tinydense.path", write_cells(tmp_path, spec))
    eps = json.loads((HERE / "configs" / "epsilon.json").read_text())
    cell.config = dict(eps, train_rows=20_000, num_features=256)
    fit = cell.module("drive", "fit")
    limit = _limit("epsilon.path", "kkt_excess")
    sound = fit.reading(cell, seed, control=False, seconds=0,
                        log=lambda m: None)
    control = fit.reading(cell, seed, control=True, seconds=0,
                          log=lambda m: None)
    assert sound["kkt_excess"] <= limit < control["kkt_excess"]


def test_serve_control_fails_where_sound_passes(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = load_cell("tinywide.docs", write_cells(tmp_path, spec))
    serve = cell.module("drive", "serve")
    limit = _limit("rcv1.docs-max", "score_gap")
    state = serve.setup(cell, 4, 2.0, lambda m: None)
    out = serve.run_window(state, 2.0, 20.0)
    sound = serve.readings(state, out)["score_gap"]
    control = serve.readings(state, out, round_bf16=True)["score_gap"]
    assert sound <= limit < control
