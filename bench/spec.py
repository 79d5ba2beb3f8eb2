"""What a cell is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's file
names its model (``bench/models/<model>.py``); the traffic file names its
entry (``bench/drivers/<entry>.py``); each per-layer metric is read by
``bench/metrics/<metric name>.py``.  Nothing here knows a cell by name.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, cell_: dict, root: Path = ROOT) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == cell_["config"])
    return json.loads((root / entry["file"]).read_text())


def traffic(cell_: dict) -> dict:
    return json.loads((BENCH / "traffic" / f"{cell_['traffic']}.json")
                      .read_text())


def applies(metric: dict, cell_name: str, e2e_names: set) -> bool:
    """Whether ``metric`` is reported in the cell: listed there, or, without
    a ``workloads`` key, wherever the metric it moves is reported."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric["moves"] in e2e_names


def e2e_metrics(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer_metrics(bench: dict, cell_name: str) -> list[dict]:
    names = {m["name"] for m in e2e_metrics(bench, cell_name)}
    return [m for m in bench["per_layer"] if applies(m, cell_name, names)]


def model(name: str):
    return importlib.import_module(f"bench.models.{name}")


def driver(entry: str):
    return importlib.import_module(f"bench.drivers.{entry}")


def reader(metric_name: str):
    """``bench/metrics/<metric_name>.py``, loaded from its file (a metric
    name may hold dots)."""
    path = BENCH / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric_name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"bench/peaks.json")
    return table[device_kind]
