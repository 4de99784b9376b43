"""BENCHMARK.json against the shape its contract fixes, and against the
files it names: a cell finds its configuration and its traffic, a per-layer
metric its file and its reader, and the two say the same."""

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_keys_names_and_lengths():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"] and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [x["name"] for x in b[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names


def test_every_cell_finds_its_files_and_the_files_agree():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    assert {w["config"] for w in b["workloads"]} == set(configs)
    for c in b["configs"]:
        doc = _load(ROOT, c["file"])
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"]
        importlib.import_module(f"benchmarks.references.{doc['reference']}")
    for w in b["workloads"]:
        mix = _load(HERE, "traffic", w["traffic"] + ".json")
        assert mix["loop"] in ("closed", "open") and mix["warm_rounds"]


def test_every_per_layer_metric_has_a_file_a_reader_and_cells_to_move():
    b = _bench()
    cells = [w["name"] for w in b["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in b["end_to_end"]}
    for m in b["per_layer"]:
        doc = _load(HERE, "metrics", m["name"] + ".json")
        # a metric's file is written once; the cells that report it grow
        # in BENCHMARK.json alone
        assert {k: doc[k] for k in m if k != "workloads"} == \
            {k: v for k, v in m.items() if k != "workloads"}
        reader = importlib.import_module(f"benchmarks.readers.{doc['reader']}")
        assert callable(reader.read)
        where = set(m.get("workloads", cells))
        assert where <= set(cells) and where <= reports[m["moves"]], m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])
        assert sum(cell in r for r in reports.values()) >= 2


def test_the_command_names_no_cell_and_no_path_outside():
    b = _bench()
    assert b["command"] == ["python3", "benchmarks/run.py"]
    with open(os.path.join(HERE, "run.py")) as f:
        text = f.read()
    for w in b["workloads"]:
        assert w["name"] not in text and w["traffic"] not in text
