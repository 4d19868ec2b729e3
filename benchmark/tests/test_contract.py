"""BENCHMARK.json against the letter of the builder's contract: what is
refused before a single run."""

import json
import os
import re

from benchlib import discover

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def spec():
    with open(os.path.join(discover.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def test_top_level_keys_and_command():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(s["paths"]) <= 16 and all(PATH.match(p) for p in s["paths"])
    assert len(s["command"]) <= 32 and all(line(w) for w in s["command"])
    assert isinstance(s["run_seconds"], int) and 1 <= s["run_seconds"] <= 51
    for w in s["command"]:
        assert not w.startswith("/") and ".." not in w.split("/")
        if "/" in w:
            assert any(w.startswith(p + "/") for p in s["paths"])


def test_configs():
    s = spec()
    assert 1 <= len(s["configs"]) <= 24
    used = {w["config"] for w in s["workloads"]}
    files = set()
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in s["paths"])
        assert c["file"] not in files and os.path.exists(
            os.path.join(discover.ROOT, c["file"]))
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        # the file names every key it changed from the source
        assert json.load(open(os.path.join(discover.ROOT, c["file"])))[
            "reduced"] == c["reduced"]
    assert len({c["name"] for c in s["configs"]}) == len(s["configs"])


def test_cells():
    s = spec()
    cells = s["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_metrics():
    s = spec()
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"]: m for m in s["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert 1 <= len(s["per_layer"]) <= 128
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    assert len(set(names)) == len(names)
    for m in s["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in s["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        moved = e2e[m["moves"]]
        reporting = set(moved.get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reporting, m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:   # setup_s, another end-to-end metric, a per-layer one
        mine = [m["name"] for m in s["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in s["per_layer"])


def test_files_under_paths_are_named_from_a_names_characters():
    s = spec()
    for p in s["paths"]:
        for d, dirs, names in os.walk(os.path.join(discover.ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for n in names:
                rel = os.path.relpath(os.path.join(d, n), discover.ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
