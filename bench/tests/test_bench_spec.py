"""``BENCHMARK.json`` against the benchmark's contract: names and units,
the files each entry names, each metric's cells and the end-to-end
metric it moves, and what every cell reports."""
from __future__ import annotations

import json
import re

import pytest

from bench_testkit import ROOT, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert len(spec["command"]) <= 32 and all(_line(w)
                                              for w in spec["command"])
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs(spec):
    assert 1 <= len(spec["configs"]) <= 24
    used = {w["config"] for w in spec["workloads"]}
    files = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_workloads(spec):
    cells = spec["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        traffic = json.loads((ROOT / "bench" / "traffic"
                              / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "drivers"
                / f"{traffic['driver']}.py").is_file()
        assert traffic["limits"], w["name"]


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert run.reader_path(m["name"]).is_file(), m["name"]
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", sorted(cells)):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m, cell)
        layers.setdefault(m["layer"], set()).add(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_cell_reports_enough(spec):
    for w in spec["workloads"]:
        cell = w["name"]
        e2e = [m["name"] for m in spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        per = [m for m in spec["per_layer"]
               if cell in m.get("workloads", [cell])]
        assert per, cell


def test_reduced_keys_are_in_the_files(spec):
    for c in spec["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in body, (c["name"], key)


@pytest.mark.parametrize("metric, reader", [
    ("device_idle.train", "device_idle.py"),
    ("device_idle.replay", "device_idle.py"),
    ("batch_rows.replay", "batch_rows.replay.py"),
    ("train_mfu", "train_mfu.py"),
])
def test_reader_found_by_name_or_stem(metric, reader):
    assert run.reader_path(metric).name == reader
