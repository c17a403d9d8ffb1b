"""The name and unit rules, and the shape of BENCHMARK.json as the
benchmark's contract fixes it."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import spec  # noqa: E402

BENCH = json.loads(spec.Layout().bench_file.read_text())
LAYOUT = spec.Layout()


@pytest.mark.parametrize(
    "name,ok",
    [
        ("web09-batch", True), ("lexical_roofline.batch", True), ("_x", True),
        ("9a", True), ("a" * 64, True), ("a" * 65, False), ("", False),
        ("a b", False), ("a,b", False), ("a/b", False), (".x", False),
        ("-x", False), ("µs", False), (None, False),
    ],
)
def test_name_rules(name, ok):
    assert spec.valid_name(name) is ok


@pytest.mark.parametrize(
    "unit,ok",
    [
        ("docs/s", True), ("%", True), ("req/s", True), ("ms", True), ("tokens/s", True),
        ("tokens per second", False), ("µs", False), ("a" * 17, False), ("", False),
    ],
)
def test_unit_rules(unit, ok):
    assert spec.valid_unit(unit) is ok


def test_benchmark_file_keeps_the_rules():
    assert spec.check_names(BENCH) == []
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert len(json.dumps(BENCH)) <= 64 * 1024


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_entries_have_just_their_keys_and_short_lines():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert (spec.REPO_ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _line(w["why"]) and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert _line(m["layer"])
    for word in BENCH["command"]:
        assert _line(word) and not word.startswith("/") and ".." not in word
    assert len(BENCH["command"]) <= 32 and 1 <= len(BENCH["paths"]) <= 16


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_every_cell_reports_what_it_must():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for cell in cells:
        mine = [n for n, m in e2e.items() if _reports(m, cell)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(_reports(m, cell) for m in BENCH["per_layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert _reports(e2e[m["moves"]], cell)
    assert {w["config"] for w in BENCH["workloads"]} == {c["name"] for c in BENCH["configs"]}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(cells) // 2)


def test_every_name_has_its_files():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(LAYOUT.module("metrics", m["name"]).read)
    for w in BENCH["workloads"]:
        cell = spec.load_cell(LAYOUT, w["name"])
        driver = LAYOUT.module("drivers", cell.traffic["driver"])
        assert all(callable(getattr(driver, f)) for f in ("setup", "window", "finish"))
        for ref in cell.config["references"]:
            LAYOUT.find("references", ref, ".py")
        assert cell.config["limits"]
