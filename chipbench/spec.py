"""Finding the benchmark's parts by name.

A cell of ``BENCHMARK.json`` names a workload file
``workloads/<cell>.json``; the workload names its configuration
``configs/<config>.json``; each per-layer metric is read by
``metrics/<metric>.py``. Adding a cell, a configuration or a metric is
adding files: nothing here lists them.

The configuration names the rest, and each is a file too:

* ``"system"``: the driver of the program under test,
  ``systems/<system>.py``, which exports ``Cell`` (see ``systems`` for
  what a cell does);
* ``"reference"`` (training configurations): the model's yardstick,
  ``<reference>.py`` beside this file, which imports nothing of the
  program and exports ``PROGRAM_FIELDS``, ``LIMITS``, ``param_shapes``,
  ``init_params``, ``Reference``, ``gaps``, ``control`` and
  ``flops_per_token`` (``mamba_ref`` is one).

Both are modules of this package, imported by name
(``chipbench.systems.<system>``, ``chipbench.<reference>``): a new system
or yardstick is a new file whose name the configuration gives; no file
that is there changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

#: the checkout's root (holds BENCHMARK.json) and the benchmark's directory
ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load_workload(name: str, base: Path = BENCH_DIR) -> Dict[str, Any]:
    return json.loads((Path(base) / "workloads" / f"{name}.json").read_text())


def load_config(name: str, base: Path = BENCH_DIR) -> Dict[str, Any]:
    return json.loads((Path(base) / "configs" / f"{name}.json").read_text())


def load_reader(metric: str, base: Path = BENCH_DIR) -> Callable[[Any], Optional[float]]:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = Path(base) / "metrics" / f"{metric}.py"
    modname = "chipbench_metric_" + metric.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_yardstick(name: str) -> ModuleType:
    """The plain reference ``chipbench.<name>`` a configuration names."""
    return importlib.import_module(f"chipbench.{name}")


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    known = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no cell {name!r} in BENCHMARK.json (known: {known})")


def cell_metrics(
    bench: Dict[str, Any], name: str
) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """(end-to-end entries, per-layer entries) that cell ``name`` reports.

    An entry with a ``workloads`` key is reported in the cells it lists; an
    end-to-end entry without one in every cell, and a per-layer entry
    without one in every cell that reports the metric it ``moves``."""
    e2e = [
        m for m in bench["end_to_end"]
        if name in m.get("workloads", [name])
    ]
    reported = {m["name"] for m in e2e}

    def reports(m: Dict[str, Any]) -> bool:
        if "workloads" in m:
            return name in m["workloads"]
        return m["moves"] in reported

    return e2e, [m for m in bench["per_layer"] if reports(m)]
