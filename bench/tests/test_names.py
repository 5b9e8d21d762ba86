"""Every name in BENCHMARK.json resolves to its files, and the file keeps
to the benchmark's contract of names and keys."""
import importlib
import json
import os
import re

import pytest

from bench import run

ROOT = run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_cell_resolves(cell):
    c, cfg, mix, entry, e2e, layers = run.load_cell(cell)
    assert NAME.match(c["name"]) and len(c["why"]) <= 200
    assert cfg["chips"] == c["chips"] in (1, 4)
    assert hasattr(entry, "Entry")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert layers
    for m in layers:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(reader.read)
        assert reader.read({"traced_windows": 0, "traced_least_s": 0.0,
                            "chips": c["chips"]}, None) is None


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {c["config"] for c in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used and c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
        n = int(cfg["participants"] * cfg["slots_per_node"]
                * cfg["load_fraction"])
        assert cfg["recordcount"] == n
    assert len({c["source"] for c in SPEC["configs"]}) == len(SPEC["configs"])


def test_metrics_keep_to_the_contract():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    cells = {c["name"] for c in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
