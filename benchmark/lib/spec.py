"""Loads BENCHMARK.json and the files it names, refusing what the driver
would refuse (names, units, keys), so a bad entry fails here and not in a
check on the chip."""

from __future__ import annotations

import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is not what the contract allows."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r}: a name is 1-64 of letters, "
                        "digits, '_', '.', '-' and starts with none of "
                        "'.', '-'")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: unit {unit!r} is not 1-16 of letters, "
                        "digits, '_', '/', '%', '.', '-'")
    return unit


def _load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except OSError as exc:
        raise SpecError(f"cannot read {path}: {exc}") from exc
    except json.JSONDecodeError as exc:
        raise SpecError(f"{path} is not JSON: {exc}") from exc


def _check_metric(m: dict, what: str, allowed_sources) -> None:
    check_name(m.get("name"), what)
    check_unit(m.get("unit"), f"{what} {m.get('name')}")
    if m.get("better") not in ("lower", "higher"):
        raise SpecError(f"{what} {m['name']}: better is lower or higher")
    if m.get("source") not in allowed_sources:
        raise SpecError(f"{what} {m['name']}: source {m.get('source')!r} "
                        f"is none of {allowed_sources}")
    for w in m.get("workloads", ()):
        check_name(w, f"{what} {m['name']} workload")


class Benchmark:
    """BENCHMARK.json with the per-cell views the harness needs."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.paths = list(self.doc.get("paths", ()))
        self.configs = {}
        for c in self.doc.get("configs", ()):
            check_name(c.get("name"), "config")
            for key in c.get("reduced", ()):
                check_name(key, f"config {c['name']} reduced key")
            self.configs[c["name"]] = c
        self.cells = {}
        for w in self.doc.get("workloads", ()):
            check_name(w.get("name"), "workload")
            check_name(w.get("config"), f"workload {w['name']} config")
            check_name(w.get("traffic"), f"workload {w['name']} traffic")
            if w["config"] not in self.configs:
                raise SpecError(f"workload {w['name']}: no config "
                                f"{w['config']!r}")
            if w.get("chips") not in (1, 4):
                raise SpecError(f"workload {w['name']}: chips is 1 or 4")
            self.cells[w["name"]] = w
        self.end_to_end = list(self.doc.get("end_to_end", ()))
        self.per_layer = list(self.doc.get("per_layer", ()))
        for m in self.end_to_end:
            _check_metric(m, "end-to-end metric", E2E_SOURCES)
        e2e_names = {m["name"] for m in self.end_to_end}
        for m in self.per_layer:
            _check_metric(m, "per-layer metric", SOURCES)
            if m.get("moves") not in e2e_names:
                raise SpecError(f"per-layer metric {m['name']}: moves "
                                f"{m.get('moves')!r} is no end-to-end metric")
        names = [m["name"] for m in self.end_to_end + self.per_layer]
        if len(set(names)) != len(names):
            raise SpecError("two metrics share a name")

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(have {sorted(self.cells)})")
        return self.cells[name]

    def metrics_for(self, cell: str, which: str) -> list[dict]:
        """The metrics of one list that this cell reports: those that
        name it under `workloads`, and those with no such key."""
        rows = self.end_to_end if which == "end_to_end" else self.per_layer
        return [m for m in rows
                if "workloads" not in m or cell in m["workloads"]]

    def _file(self, *parts: str) -> str:
        path = os.path.normpath(os.path.join(self.root, *parts))
        homes = [os.path.join(self.root, p) + os.sep for p in self.paths]
        if not any(path.startswith(home) for home in homes):
            raise SpecError(f"{path} lies outside paths {self.paths}")
        return path

    def config_file(self, cell: str) -> dict:
        return _load_json(self._file(
            self.configs[self.cell(cell)["config"]]["file"]))

    def traffic_file(self, cell: str) -> dict:
        return _load_json(self._file(
            self.paths[0], "traffic", self.cell(cell)["traffic"] + ".json"))

    def metric_file(self, name: str) -> dict:
        doc = _load_json(self._file(self.paths[0], "metrics", name + ".json"))
        check_name(doc.get("reader"), f"metric {name} reader")
        return doc
