"""Find a cell and everything it names, by name, in files of their own.

``BENCHMARK.json`` at the root of the repository lists configurations,
cells (``workloads``) and metrics. For a cell the harness reads

* ``configs/<config>.json``  -- the deployment's sizes, guarantee and limits;
* ``traffic/<traffic>.json`` -- the traffic mix's parameters, which name the
  driver that generates it;
* ``drivers/<driver>.py``    -- one general generator per kind of traffic;
* ``metrics/<metric>.py``    -- one reader per metric, end to end or per layer;
* ``counts/<kernel>.py``     -- a kernel's bytes and operations from shapes;
* ``references/<name>.py``   -- a configuration's plain reference;
* ``peaks.json``             -- the device table, keyed by ``device_kind``.

A later cell, configuration or metric is new files and new entries; no file
that is there needs an edit. :class:`Layout` searches several roots in order,
which is how the tests add a dummy cell without touching these directories.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

BENCH_ROOT = Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_ROOT.parents[1]

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def valid_name(name: str) -> bool:
    return isinstance(name, str) and NAME_RE.fullmatch(name) is not None


def valid_unit(unit: str) -> bool:
    return isinstance(unit, str) and UNIT_RE.fullmatch(unit) is not None


def check_names(bench: dict) -> list[str]:
    """Every name, unit, better and source rule the file must keep; returns
    the breaches (empty when the file is sound)."""
    bad = []

    def name(kind, value):
        if not valid_name(value):
            bad.append(f"{kind} {value!r} is not a valid name")

    for c in bench.get("configs", []):
        name("config", c.get("name"))
        for key in c.get("reduced", []):
            name("reduced key", key)
    for w in bench.get("workloads", []):
        for key in ("name", "config", "traffic"):
            name(f"workload {key}", w.get(key))
    seen = set()
    for group in ("end_to_end", "per_layer"):
        for m in bench.get(group, []):
            name("metric", m.get("name"))
            if m.get("name") in seen:
                bad.append(f"metric {m.get('name')!r} appears twice")
            seen.add(m.get("name"))
            if not valid_unit(m.get("unit")):
                bad.append(f"unit {m.get('unit')!r} of {m.get('name')!r}")
            if m.get("better") not in ("lower", "higher"):
                bad.append(f"better {m.get('better')!r} of {m.get('name')!r}")
            if m.get("source") not in SOURCES:
                bad.append(f"source {m.get('source')!r} of {m.get('name')!r}")
    return bad


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where the benchmark's files are: ``bench_file`` and the roots that
    the per-name files are searched in, first match wins."""

    bench_file: Path = REPO_ROOT / "BENCHMARK.json"
    roots: tuple[Path, ...] = (BENCH_ROOT,)

    def find(self, kind: str, name: str, suffix: str) -> Path:
        if not valid_name(name):
            raise ValueError(f"{kind} name {name!r} breaks the name rules")
        for root in self.roots:
            path = Path(root) / kind / f"{name}{suffix}"
            if path.is_file():
                return path
        raise FileNotFoundError(f"no {kind}/{name}{suffix} under {list(self.roots)}")

    def benchmark(self) -> dict:
        with open(self.bench_file) as f:
            bench = json.load(f)
        bad = check_names(bench)
        if bad:
            raise ValueError("BENCHMARK.json: " + "; ".join(bad))
        return bench

    def json(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str) -> ModuleType:
        path = self.find(kind, name, ".py")
        mod_name = "chipbench_" + kind + "_" + re.sub(r"\W", "_", str(path))
        if mod_name in sys.modules:
            return sys.modules[mod_name]
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod  # dataclasses look their module up by name
        spec.loader.exec_module(mod)
        return mod

    def peaks(self, device_kind: str) -> dict:
        for root in self.roots:
            path = Path(root) / "peaks.json"
            if path.is_file():
                with open(path) as f:
                    table = json.load(f)["devices"]
                if device_kind in table:
                    return table[device_kind]
        raise KeyError(f"device kind {device_kind!r} is not in the table of peaks")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of BENCHMARK.json with what it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(layout: Layout, workload: str) -> Cell:
    bench = layout.benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has {sorted(by_name)}")
    w = by_name[workload]
    if w["config"] not in {c["name"] for c in bench["configs"]}:
        raise KeyError(f"workload {workload!r} names no listed config {w['config']!r}")
    config = layout.json("configs", w["config"])
    if config.get("name") != w["config"]:
        raise ValueError(f"configs/{w['config']}.json names {config.get('name')!r}")
    traffic = layout.json("traffic", w["traffic"])
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, workload)),
    )
