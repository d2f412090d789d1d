"""Find a cell's pieces by name and assemble its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``chipbench/configs/<config>.json``: the configuration's sizes;
* ``chipbench/traffic/<traffic>.json``: the mix's parameters, with
  ``"driver"`` naming the general generator and timed loop that reads them
  (``chipbench/drivers/<driver>.py``);
* ``chipbench/metrics/<metric>.py``: one per-layer metric, a ``read(run)``
  function that returns a number or ``None`` when it finds nothing to read.

A later cell, mix, configuration or metric is added as files and entries;
no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent


class CellError(RuntimeError):
    """The benchmark cannot run this cell as asked."""


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: pathlib.Path) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise CellError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: dict, name: str, bench_dir: pathlib.Path = BENCH_DIR
              ) -> Cell:
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; "
                        f"cells: {sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = bench_dir.parent / configs[w["config"]]["file"]
    config = json.loads(cfg_path.read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name) and m["moves"] in e2e_names]
    return Cell(name, w, config, traffic, e2e, per_layer)


def load_driver(cell: Cell):
    name = cell.traffic["driver"]
    return importlib.import_module(f"chipbench.drivers.{name}")


def load_reader(metric: str, bench_dir: pathlib.Path = BENCH_DIR
                ) -> Callable[[Any], Optional[float]]:
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise CellError(f"no reader for per-layer metric {metric!r} "
                        f"at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def percentile(values, q: float) -> float:
    """The q-th percentile of every sample (numpy's linear interpolation)."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("no samples")
    return float(np.percentile(v, q))


@dataclasses.dataclass
class Check:
    """One number compared against its limit: correct while value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(math.isfinite(self.value) and self.value <= self.limit)


def result_line(*, checks: List[Check], attempted: int, failed: int,
                metrics: Dict[str, tuple], device: dict,
                breakdown: Optional[dict] = None) -> dict:
    """The contract's last line: ``checks`` comes last."""
    out = {
        "correct": bool(failed == 0 and all(c.ok for c in checks)),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": float(c.value),
                              "limit": float(c.limit)} for c in checks}
    return out


def check_lines(checks: List[Check]) -> List[str]:
    return [f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}" for c in checks]
