"""Find a cell's pieces by name: everything here is data or a small file.

* ``BENCHMARK.json`` at the checkout's root lists the cells
  (``workloads``), each naming a configuration and a traffic mix, and the
  per-layer metrics with the cells that report them;
* ``configs/<config>.json`` holds the configuration as it is run;
* ``traffic/<mix>.json`` holds the mix the generator reads;
* ``limits/<cell>.json`` holds the limits of the correctness check;
* ``metrics/<metric>.py`` is the reader of one per-layer metric: a
  ``read(ctx)`` that returns a number, or None where it finds nothing; a
  quantity split by cell, ``<quantity>.<part>``, is read by
  ``metrics/<quantity>.py`` unless a file of its own full name exists;
* ``references/<name>.py`` is a configuration's plain reference;
* ``peaks.json`` holds the chips' peaks by ``device_kind``.

A later cell, configuration, mix or metric is added by adding files and
entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json — known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, base: Path = HERE) -> dict:
    return load_json(base / "configs" / f"{name}.json")


def traffic(name: str, base: Path = HERE) -> dict:
    return load_json(base / "traffic" / f"{name}.json")


def limits(cell_name: str, base: Path = HERE) -> dict:
    return load_json(base / "limits" / f"{cell_name}.json")


def peak(device_kind: str, base: Path = HERE) -> dict:
    table = load_json(base / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def _load_module(path: Path, tag: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{tag}_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, base: Path = HERE):
    path = base / "metrics" / f"{name}.py"
    if not path.is_file():
        path = base / "metrics" / f"{name.split('.')[0]}.py"
    return _load_module(path, "metric").read


def reference(name: str, base: Path = HERE):
    return _load_module(base / "references" / f"{name}.py", "ref")


def per_layer_metrics(bench: dict, cell_name: str):
    """The per-layer metrics this cell reports, in BENCHMARK.json order."""
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def end_to_end_metrics(bench: dict, cell_name: str):
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]
