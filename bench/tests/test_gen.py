"""The generators: the dense generator is seeded and every seed permutes
one problem; the path's work count depends on the configuration alone."""
import numpy as np
import pytest

from bench.gen import dense
from bench.harness import load_module, HERE


def test_seeds_permute_one_problem():
    cfg = {"train_rows": 64, "num_features": 40}
    x, y = np.asarray(dense.make(cfg, 1)["X"]), np.asarray(dense.make(cfg, 2)["X"])
    assert not np.array_equal(x, y)
    np.testing.assert_array_equal(x[np.lexsort(x.T)], y[np.lexsort(y.T)])


def test_dense_generator_is_seeded():
    cfg = {"train_rows": 64, "num_features": 40}
    a, b = dense.make(cfg, 4), dense.make(cfg, 4)
    np.testing.assert_array_equal(np.asarray(a["X"]), np.asarray(b["X"]))
    assert set(np.unique(np.asarray(a["y"]))) <= {-1.0, 1.0}


@pytest.mark.parametrize("gen,cfg", [
    (dense, {"train_rows": 400_000, "num_features": 2000, "path_len": 12}),
])
def test_path_mfu_counts_work_from_the_config_alone(gen, cfg):
    mfu = load_module(HERE / "metrics" / "path_mfu.py")
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    facts = {"config": cfg, "gen": gen, "peaks": peaks, "path_s": 1.0}
    passes = cfg["path_len"] + 1
    expect = gen.live_bytes(cfg) * passes / 819e9 * 100
    assert mfu.read(facts) == pytest.approx(expect)
    # the same config with another grid length moves it in proportion
    longer = dict(cfg, path_len=2 * cfg["path_len"])
    assert mfu.read(dict(facts, config=longer)) == pytest.approx(
        expect * (2 * cfg["path_len"] + 1) / passes)
    # nothing the implementation does enters: only config, peaks, time
    assert mfu.read(dict(facts, path_s=2.0)) == pytest.approx(expect / 2)
