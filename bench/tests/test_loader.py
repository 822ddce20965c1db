"""New cells are data: a tiny cell defined only by files in a temporary
directory is found by name and driven in-process on the CPU."""
import json
import time

import pytest

from bench.harness import ROOT, load_cell, run_cell
from bench.tests.cells import CELLS, write_cells


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return write_cells(tmp_path_factory.mktemp("checkout"), spec)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_tiny_cell_found_and_driven(tiny_root, name):
    cell = load_cell(name, tiny_root)
    assert cell.config["num_features"] > 0
    res = run_cell(cell, seed=2**31 + 7, seconds=2.0, trace=False,
                   t_start=time.perf_counter())
    assert res["correct"], res
    e2e = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == e2e
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0


def test_unknown_cell_is_refused(tiny_root):
    with pytest.raises(SystemExit):
        load_cell("nosuch.cell", tiny_root)
