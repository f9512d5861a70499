"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names every cell, configuration
and metric.  Each piece lives in a file of its own, found by its name:

  * a configuration: the file its entry names (``configs/<name>.json``);
  * a traffic mix: ``traffic/<name>.json``;
  * a configuration's code: the modules its ``"reference"`` and, where it
    has one, ``"network"`` keys name (``configs/<module>.py``);
  * a metric: ``metrics/<name>.py``, whose ``read(run)`` returns the
    number, or None when the run holds nothing to read.  A metric split by
    the end-to-end metric it moves (``device_idle.flood``) falls back to the
    reader of the name before its first dot (``metrics/device_idle.py``).

So a later change adds a cell, a mix or a metric by adding files and
entries, never by editing one that is already there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import types
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CONFIGS_DIR = BENCH_DIR / "configs"   # configurations' modules


@dataclasses.dataclass
class Cell:
    """One workload entry with its configuration, traffic and metrics."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    modules: Path             # where its configuration's modules are


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    listed = metric.get("workloads")
    return listed is None or workload in listed


def load_traffic(name: str) -> dict:
    with open(BENCH_DIR / "traffic" / f"{name}.json") as f:
        traffic = json.load(f)
    traffic["name"] = name
    return traffic


def load_cell(workload: str, root: Path = ROOT,
              modules: Path = CONFIGS_DIR) -> Cell:
    """The workload ``workload`` of ``BENCHMARK.json``; KeyError if absent."""
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[entry["config"]]["file"]) as f:
        config = json.load(f)
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=config,
        traffic=load_traffic(entry["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        modules=modules,
    )


def _exec_file(path: Path, prefix: str, name: str) -> types.ModuleType:
    """The module in ``path``, loaded by path, since a name may hold dots
    and dashes, and registered in ``sys.modules``, where a dataclass in it
    looks its module up."""
    module_name = f"{prefix}_{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def load_module(name: str, directory: Path = CONFIGS_DIR
                ) -> types.ModuleType:
    """A configuration's module ``<directory>/<name>.py`` (its
    ``"reference"`` or ``"network"``)."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no module {name!r} for the configuration: no {path}")
    return _exec_file(path, "bench_config", name)


def load_reader(metric: str) -> Callable[[object], Optional[float]]:
    """``metrics/<metric>.py``'s ``read``, else that of the name before the
    first dot."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH_DIR / "metrics" / f"{metric.split('.')[0]}.py"
    return _exec_file(path, "bench_metric", metric).read


def read_metrics(metrics: List[dict], run) -> Dict[str, dict]:
    """Each metric's reader applied to ``run``; those that find nothing to
    read are left out."""
    out = {}
    for m in metrics:
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
