"""Find the benchmark's pieces by name.

Every configuration, traffic mix, graph family and per-layer metric is a
file of its own under ``benchmarks/chip``, named after the name that
``BENCHMARK.json`` or a configuration gives it:

* ``configs/<config>.json``   sizes, settings and correctness limits
* ``traffic/<traffic>.json``  the mix: its loop's name and parameters
* ``loops/<loop>.py``         ``run(ctx)``: the schedule of a traffic mix
* ``graphs/<family>.py``      ``generate(n, ..., seed)`` -> (points or
                              None, edge tails, edge heads), keyword
                              arguments from the configuration's graph
* ``references/<name>.py``    ``partition(points, csr, k, skew=0.0)``,
                              the quality reference a configuration names
* ``metrics/<metric>.py``     ``read(obs)`` -> number or None

A later change adds a piece by adding its file and its entry; nothing
here names one. An unknown name is an error, never a default.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


class UnknownName(LookupError):
    """A name that no file or entry of the benchmark defines."""


def _checked(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise UnknownName(f"{kind} name {name!r} is not a valid name")
    return name


def _file(kind: str, subdir: str, name: str, suffix: str) -> Path:
    path = BENCH_DIR / subdir / (_checked(kind, name) + suffix)
    if not path.is_file():
        raise UnknownName(f"unknown {kind} {name!r}: no {path.relative_to(ROOT)}")
    return path


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _module(kind: str, path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', path.stem)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _json(Path(root) / "BENCHMARK.json")


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    _checked("workload", name)
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise UnknownName(f"unknown workload {name!r}; BENCHMARK.json has "
                      f"{sorted(w['name'] for w in bench['workloads'])}")


def config(name: str) -> Dict[str, Any]:
    return _json(_file("config", "configs", name, ".json"))


def traffic(name: str) -> Dict[str, Any]:
    return _json(_file("traffic", "traffic", name, ".json"))


def loop(name: str) -> ModuleType:
    return _module("loop", _file("loop", "loops", name, ".py"))


def quality_reference(name: str) -> ModuleType:
    return _module("reference", _file("quality reference", "references",
                                      name, ".py"))


def graph_family(name: str) -> ModuleType:
    return _module("graph", _file("graph family", "graphs", name, ".py"))


def metric_reader(name: str) -> Callable:
    return _module("metric", _file("metric", "metrics", name, ".py")).read


def peaks(device_kind: str) -> Dict[str, Any]:
    table = _json(BENCH_DIR / "peaks.json")
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise UnknownName(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(table['devices'])})") from None
