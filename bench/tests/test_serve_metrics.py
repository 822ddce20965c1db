"""The serving readers of the program's spans, on hand-made span lists:
each reads its value where the spans are, and None where they are not
(as on a program that records no such span)."""
import pytest

from bench.harness import HERE, load_module


def _sp(sid, name, dur, parent=None, **args):
    return {"name": name, "ts": 0.0, "dur": dur, "tid": 0, "sid": sid,
            "parent": parent, "args": args}


SPANS = [
    _sp(1, "encode", 1e-4, req=0),
    _sp(2, "encode", 1e-4, req=1),
    _sp(3, "drain", 2e-3, batch=0, drained=2, wait_s=0.010, req=[0, 1]),
    _sp(4, "pack", 1.5e-3, parent=3, batch=0),
    _sp(5, "score", 20e-3, batch=0, rows=2),
    _sp(6, "put", 4e-3, parent=5, batch=0, bytes=6_000_000),
    _sp(7, "launch", 1e-3, parent=5, batch=0),
    _sp(8, "fetch", 14e-3, parent=5, batch=0),
    # a drain that drained nothing: its pack and zero wait are skipped
    _sp(9, "drain", 9e-3, batch=1, drained=0, wait_s=0.0),
    _sp(10, "pack", 8e-3, parent=9, batch=1),
    _sp(11, "drain", 1e-3, batch=2, drained=4, wait_s=0.050, req=[2, 5]),
    _sp(12, "pack", 0.5e-3, parent=11, batch=2),
    _sp(13, "score", 22e-3, batch=2, rows=4),
    _sp(14, "put", 6e-3, parent=13, batch=2, bytes=7_000_000),
    _sp(15, "launch", 1e-3, parent=13, batch=2),
    _sp(16, "fetch", 12e-3, parent=13, batch=2),
]

# the parent program's spans: score and drain, no children or new args
OLD = [_sp(1, "encode", 1e-4), _sp(2, "drain", 2e-3, drained=2),
       _sp(3, "score", 20e-3, rows=2)]


@pytest.mark.parametrize("name,want", [
    ("pack_ms", (1.5 + 0.5) / 2),
    ("queue_wait_ms", 1e3 * (0.010 + 0.050) / (2 + 4)),
    ("put_ms", (4 + 6) / 2),
    ("fetch_ms", (14 + 12) / 2),
    ("h2d_mb_per_batch", (6.0 + 7.0) / 2),
])
def test_reader_value_and_absence(name, want):
    read = load_module(HERE / "metrics" / f"{name}.py").read
    assert read({"spans": SPANS}) == pytest.approx(want)
    assert read({"spans": OLD}) is None
    assert read({"spans": []}) is None


def test_drains_of_nothing_give_no_pack_or_wait():
    idle = [_sp(1, "drain", 9e-3, batch=0, drained=0, wait_s=0.0),
            _sp(2, "pack", 8e-3, parent=1, batch=0)]
    for name in ("pack_ms", "queue_wait_ms"):
        read = load_module(HERE / "metrics" / f"{name}.py").read
        assert read({"spans": idle}) is None
