"""Finds what ``BENCHMARK.json`` names, by name, under this directory.

  configs/<config>.json      a model configuration as it is run; its
                             ``arch`` names its architecture module
  archs/<arch>.py            an architecture: the program's config, the
                             weights' leaves, the float32 reference layer
                             and the work counts (``archs/dense.py`` says
                             what a module gives)
  traffic/<traffic>.json     a traffic mix for the one generator
  metrics/<metric>.py        the reader of a metric; ``a.b`` is read by
                             ``metrics/a.py`` (one reader serves every
                             cell group that a suffix names)
  peaks.json                 the chips' peaks, keyed by ``device_kind``

An architecture, a configuration, a mix or a metric is added with a file
and an entry in ``BENCHMARK.json``; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


class UnknownDevice(KeyError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, base: Path = HERE) -> dict:
    return load_json(base / "configs" / f"{name}.json")


def traffic(name: str, base: Path = HERE) -> dict:
    return load_json(base / "traffic" / f"{name}.json")


def peaks(device_kind: str, base: Path = HERE) -> dict:
    table = load_json(base / "peaks.json")
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device_kind {device_kind!r} in "
                            f"peaks.json; have {sorted(table)}")
    return table[device_kind]


def metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def _module(kind: str, stem: str, base: Path, what: str):
    path = base / kind / f"{stem}.py"
    if not path.is_file():
        raise KeyError(f"no {kind}/{path.name} for {what}")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.chip.{kind}.{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


_LOADED: dict = {}


def reader(metric: str, base: Path = HERE):
    """The ``read(run)`` function of a metric's reader file."""
    return _module("metrics", metric.split(".")[0], base,
                   f"metric {metric!r}").read


def arch(name: str, base: Path = HERE):
    """The architecture module ``archs/<name>.py``."""
    return _module("archs", name, base, f"architecture {name!r}")
