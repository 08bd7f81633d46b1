"""The benchmark's definition, read from ``BENCHMARK.json`` and found by name.

Nothing here knows a cell. A cell names a configuration and a traffic mix,
and a per-layer metric names a reader; each is a file of its own:

    <bench>/configs/<config>.json   one deployment (its path is the
                                    configuration's ``file`` entry)
    <bench>/traffic/<mix>.json      one traffic mix, read by the generator
                                    module it names: <bench>/traffic/<g>.py
    <bench>/predicates/<p>.py       the predicate a mix names: its NumPy
                                    semantics for the reference and the
                                    program's predicate objects
    <bench>/metrics/<metric>.py     one reader per per-layer metric
    <bench>/peaks.json              published chip peaks by device_kind

``<bench>`` is the first entry of ``paths``. A later change adds a cell, a
mix or a metric as new files plus new entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType


class SpecError(ValueError):
    """A name that ``BENCHMARK.json`` or the files under ``paths`` lack."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic file's contents, with "name" added
    chips: int
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: str, name: str) -> ModuleType:
    """Import one file by path (metric and generator files are named after
    their entries, which may hold dots)."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    mod_name = "bench_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Spec:
    """``BENCHMARK.json`` under ``root`` and the files it names."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.doc = _load_json(os.path.join(self.root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(self.root, self.doc["paths"][0])
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}

    def path(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    def config(self, name: str) -> dict:
        entry = self.configs.get(name)
        if entry is None:
            raise SpecError(f"no configuration {name!r} in BENCHMARK.json")
        cfg = _load_json(os.path.join(self.root, entry["file"]))
        if cfg.get("name") != name:
            raise SpecError(f"{entry['file']} holds {cfg.get('name')!r}, "
                            f"not {name!r}")
        return cfg

    def traffic(self, name: str) -> dict:
        mix = _load_json(self.path("traffic", name + ".json"))
        return {**mix, "name": name}

    def generator(self, traffic: dict) -> ModuleType:
        g = traffic["generator"]
        return load_module(self.path("traffic", g + ".py"), "traffic_" + g)

    def predicate(self, traffic: dict) -> ModuleType:
        p = traffic["predicate"]
        mod = load_module(self.path("predicates", p + ".py"), "predicate_" + p)
        for fn in ("meets", "program"):
            if not callable(getattr(mod, fn, None)):
                raise SpecError(f"predicates/{p}.py has no {fn}()")
        return mod

    def metric_reader(self, name: str) -> ModuleType:
        mod = load_module(self.path("metrics", name + ".py"), "metric_" + name)
        if not callable(getattr(mod, "read", None)):
            raise SpecError(f"metrics/{name}.py has no read(run)")
        return mod

    def peaks(self, device_kind: str) -> dict:
        table = _load_json(self.path("peaks.json"))["devices"]
        if device_kind not in table:
            raise SpecError(f"no published peaks for device {device_kind!r} "
                            "in peaks.json")
        return table[device_kind]

    def cell(self, name: str) -> Cell:
        w = self.workloads.get(name)
        if w is None:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(have {sorted(self.workloads)})")
        return Cell(
            name=name,
            config=self.config(w["config"]),
            traffic=self.traffic(w["traffic"]),
            chips=int(w["chips"]),
            end_to_end=tuple(m for m in self.doc["end_to_end"]
                             if _applies(m, name)),
            per_layer=tuple(m for m in self.doc["per_layer"]
                            if _applies(m, name)),
        )


def find_root() -> str:
    """The checkout that holds this benchmark: the parent of ``bench/``."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
