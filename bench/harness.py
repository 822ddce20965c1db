"""The benchmark harness: finds a cell's pieces by name and runs it.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix, cell or per-layer metric is a
file of its own, found by its name:

* ``bench/configs/<config>.json``: sizes, source, ``reduced``,
  ``assumed`` and ``generator``, the module in ``bench/gen/`` that makes
  its data from the seed;
* ``bench/traffic/<traffic>.json``: the mix's parameters and ``driver``,
  the module in ``bench/drive/`` that runs it;
* ``bench/limits/<cell>.json``: the numbers that decide ``correct``, each
  with its limit and the readings the limit was set from;
* ``bench/metrics/<metric>.py``: ``read(facts)`` returns the per-layer
  metric from what the drive module's traced run gathered, or None.

Data files are looked up under the given root; code modules there first
and then beside this file, so a new cell needs only new files and an
entry in ``BENCHMARK.json``.

A driver module has ``setup(cell, seed, seconds, log) -> state``, ``measure(state,
seconds) -> Window``, ``traced(state, seconds, capture) -> Window`` and
``check(state, window) -> list[Check]``; ``check`` frees the program's
state before it runs the plain reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dirs: List[Path]

    def module(self, kind: str, name: str):
        """``bench/<kind>/<name>.py`` from the first bench dir holding it."""
        for d in self.bench_dirs:
            path = d / kind / f"{name}.py"
            if path.is_file():
                return load_module(path)
        raise FileNotFoundError(f"no {kind}/{name}.py under "
                                f"{[str(d) for d in self.bench_dirs]}")


@dataclass
class Check:
    """One number compared with its limit (``value <= limit`` passes)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Window:
    """What a driver's window produced."""
    metrics: Dict[str, float]                # end-to-end values
    attempted: int
    failed: int
    outputs: object = None                   # what check() compares
    facts: dict = field(default_factory=dict)  # inputs of metric readers
    notes: List[str] = field(default_factory=list)


_MODULES: Dict[Path, object] = {}


def load_module(path: Path):
    path = Path(path).resolve()
    mod = _MODULES.get(path)
    if mod is None:
        name = "bench_" + "_".join(path.parts[-2:]).replace(".", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return mod


def _read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    root = Path(root)
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {root / 'BENCHMARK.json'}"
                         f"; have {sorted(cells)}")
    w = cells[name]
    bench = root / "bench"

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    limits_path = bench / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(bench / "configs" / f"{w['config']}.json"),
        traffic=_read_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(limits_path) if limits_path.is_file() else {},
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
        bench_dirs=[bench] + ([HERE] if bench.resolve() != HERE else []),
    )


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = _read_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][device_kind]


class CompileCounter:
    """Counts executables built while active: compiles and loads from the
    persistent cache alike. A warmed window has none."""

    _EVENTS = ("/jax/compilation_cache/cache_hits",)
    _DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __enter__(self) -> "CompileCounter":
        import jax

        self.count = 0
        self._active = True
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def _event(self, event: str, **kw) -> None:
        if self._active and event in self._EVENTS:
            self.count += 1

    def _duration(self, event: str, duration: float, **kw) -> None:
        if self._active and event in self._DURATIONS:
            self.count += 1

    def __exit__(self, *exc) -> bool:
        self._active = False
        return False


class GcPauses:
    """Times the collector's passes while active, so that a stall in the
    window can be told from a collection."""

    def __enter__(self) -> "GcPauses":
        self.count, self.longest, self.gen = 0, 0.0, -1
        self._t0 = None
        gc.callbacks.append(self._cb)
        return self

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt = time.perf_counter() - self._t0
            self.count += 1
            if dt > self.longest:
                self.longest, self.gen = dt, info["generation"]

    def __exit__(self, *exc) -> bool:
        gc.callbacks.remove(self._cb)
        return False

    def note(self) -> str:
        return (f"# gc: {self.count} passes in the window, longest "
                f"{self.longest:.6f} s (generation {self.gen})")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def checks_of(cell: Cell, readings: Dict[str, float]) -> List[Check]:
    """The readings the cell's limits file names, each beside its limit.
    A number named there but not read is a failed check."""
    return [Check(k, float(readings.get(k, math.inf)), float(v["limit"]))
            for k, v in cell.limits.items()]


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float,
             device_info: Optional[Callable[[], dict]] = None) -> dict:
    """Set up, measure (or trace), read memory, check; the result line."""
    driver = cell.module("drive", cell.traffic["driver"])
    state = driver.setup(cell, seed, seconds, log)
    # what set-up made (documents, handles) stays alive all run: keep it
    # out of the collector's full passes, which would stall the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"# setup_s {setup_s!r}")
    if trace:
        from bench.capture import Capture

        win = driver.traced(state, seconds,
                            Capture(ROOT / ".bench_trace" / cell.name))
    else:
        win = driver.measure(state, seconds)
    device = device_info() if device_info else {}
    for note in win.notes:
        log(note)
    checks = driver.check(state, win)
    correct = bool(checks) and all(c.ok for c in checks) \
        and win.failed == 0 and win.attempted > 0
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = cell.module("metrics", m["name"]).read(win.facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(win.facts.get("device", {}))
    else:
        values = dict(win.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if trace and win.facts.get("breakdown"):
        result["breakdown"] = win.facts["breakdown"]
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    return result
