"""BENCHMARK.json and the files the harness finds by name."""
import json
import re

import pytest

from bench import harness
from bench.compare import NAMES
from bench.reference import model_from

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    c = harness.load_cell(cell)
    assert c.config["chips"] == c.chips
    assert c.config["drives"] % c.chips == 0
    harness.program(c)                       # the program accepts it
    model_from(c.config, c.traffic)          # the reference models it
    assert set(harness.load_limits(c.config["name"])) == set(NAMES)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.metric_reader(metric["name"]))


def test_configs_list_their_cuts():
    for c in SPEC["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert {"addresses", "request_bytes", "mapping_hit_rate"} <= set(
            cfg["assumed"])
        assert cfg["deployment"] and cfg["source"]
