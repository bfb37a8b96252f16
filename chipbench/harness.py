"""The benchmark's registry and the record of one run.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``BENCHMARK.json``'s ``configs[].file``: the deployment (JSON);
- ``chipbench/traffic/<traffic>.json``: the traffic mix, whose
  ``driver`` key names the general driver that reads it
  (``chipbench/drivers/<driver>.py``);
- ``chipbench/limits/<workload>.json``: the limits of the numbers the
  cell's correctness check compares;
- ``chipbench/metrics/<metric>.py``: one reader per per-layer metric,
  ``read(run) -> float | None``. A metric split by cell, named
  ``<base>.<cells>``, is read by its base's reader; an end-to-end metric
  so named takes the value its driver reports as ``<base>``.

A new configuration, mix, cell or metric is new files and new manifest
entries; nothing here changes.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
SPAN_PREFIX = "chipbench."


class ManifestError(Exception):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def find_cell(manifest: dict, workload: str, root: Path = ROOT
              ) -> Tuple[dict, dict, dict]:
    """(cell, configuration, traffic) of one workload, from their files."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise ManifestError(f"unknown workload {workload!r}; "
                            f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def load_limits(workload: str) -> dict:
    return load_json(HERE / "limits" / f"{workload}.json")


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise ManifestError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str):
    return _load_module(HERE / "drivers" / f"{name}.py",
                        f"chipbench.drivers.{name}")


def base_names(name: str) -> List[str]:
    """``assess.tick_ms.one_job`` → itself, ``assess.tick_ms``, ``assess``:
    a metric split by cell (``<base>.<cells>``) is read as its base."""
    parts = name.split(".")
    return [".".join(parts[:k]) for k in range(len(parts), 0, -1)]


def load_metric(name: str):
    """The reader of a per-layer metric: ``metrics/<name>.py``, or that of
    the metric it was split from."""
    for base in base_names(name):
        path = HERE / "metrics" / f"{base}.py"
        if path.is_file():
            return _load_module(path, "chipbench_metric_"
                                + base.replace(".", "_"))
    raise ManifestError(f"no reader for metric {name!r}")


def cell_metrics(manifest: dict, workload: str, trace: bool
                 ) -> List[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in manifest[key]
            if workload in m.get("workloads", [workload])]


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield


class Run:
    """What one run of one cell records: counters, end-to-end values,
    notes printed on earlier lines, and the numbers compared."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 trace: bool, hooks: Optional[Dict[str, Callable]] = None):
        self.workload = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.hooks = dict(hooks or {})
        self.counters: Dict[str, object] = {}
        self.e2e: Dict[str, float] = {}
        self.checks: List[Tuple[str, float, float, bool]] = []
        self.trace_summary: Optional[dict] = None
        self.attempted = 0
        self.config: dict = {}
        self.device_kind = ""
        self.device_count = 1

    def seeds(self, k: int) -> List[int]:
        """``k`` independent 32-bit seeds drawn from ``--seed``."""
        ss = np.random.SeedSequence(self.seed)
        return [int(c.generate_state(1)[0]) for c in ss.spawn(k)]

    def hook(self, name: str, value):
        """Test seam: a hook may replace part of the system under test
        (a broken backend, a broken step) to see ``correct`` fail."""
        fn = self.hooks.get(name)
        return fn(value) if fn is not None else value

    @staticmethod
    def note(msg: str) -> None:
        print(msg, flush=True)

    def check(self, name: str, value: float, limit: float) -> None:
        value = float(value)
        self.checks.append((name, value, float(limit),
                            bool(value <= limit)))

    def check_at_least(self, name: str, value: float, limit: float
                       ) -> None:
        value = float(value)
        self.checks.append((name, value, float(limit),
                            bool(value >= limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(ok for *_, ok in self.checks)
