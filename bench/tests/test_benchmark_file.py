"""``BENCHMARK.json`` against the benchmark's contract, and every file
it names found by name."""
from __future__ import annotations

import json
import re

import pytest

from harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
B = cells.benchmark()


def chip_rule_errors(workloads: list, configs: dict) -> list:
    """Where the cells break the layout rules: a cell takes 1 or 4
    chips, as many as its configuration's ``mesh.devices`` (1 without a
    ``mesh``), on the ``data`` axis, and no more than half of the cells,
    rounded down, or one, take 4."""
    errors = []
    for w in workloads:
        config = configs[w["config"]]
        if w["chips"] not in (1, 4):
            errors.append(f"{w['name']}: {w['chips']} chips")
        if w["chips"] != cells.chips(config):
            errors.append(f"{w['name']}: {w['chips']} chips for a "
                          f"{cells.chips(config)}-chip configuration")
        if "mesh" in config and config["mesh"].get("axis") != "data":
            errors.append(f"{w['name']}: mesh axis "
                          f"{config['mesh'].get('axis')!r}")
    four = sum(w["chips"] == 4 for w in workloads)
    if four > max(1, len(workloads) // 2):
        errors.append(f"{four} of {len(workloads)} cells take 4 chips")
    return errors


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) < 64 * 1024


def test_names_units_and_entry_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in B[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert cells.load_json(cells.ROOT / c["file"])["reduced"] == \
            c["reduced"]
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
        assert (cells.BENCH / "traffic" / f"{w['traffic']}.json").exists()
    configs = {c["name"]: cells.load_json(cells.ROOT / c["file"])
               for c in B["configs"]}
    assert chip_rule_errors(B["workloads"], configs) == []
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert UNIT.match(m["unit"]) and m["source"] == "host_clock"
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert UNIT.match(m["unit"])
        assert (cells.BENCH / "layers" / f"{m['name']}.py").exists()


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    for w in B["workloads"]:
        mine = {m["name"] for m in cells.metrics_of(w["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layers = cells.metrics_of(w["name"], "per_layer")
        assert layers
        for m in layers:
            # A layer metric moves an end-to-end metric its cells report.
            assert w["name"] in e2e[m["moves"]].get("workloads",
                                                    [w["name"]])


def test_every_layer_reader_and_kernel_cost_loads():
    for m in B["per_layer"]:
        assert callable(cells.load_module("layers", m["name"]).read)
    for kernel in ("cms_update", "quantile_compact"):
        assert callable(cells.load_module("costs", kernel).cost)


def _cells(*chips_and_configs):
    return [{"name": f"c{i}", "config": config, "chips": chips}
            for i, (chips, config) in enumerate(chips_and_configs)]


@pytest.mark.parametrize("workloads, broken", [
    (_cells((1, "one")), False),
    (_cells((4, "mesh4"), (1, "one")), False),
    (_cells((4, "mesh4")), False),
    (_cells((4, "one"), (1, "one")), True),
    (_cells((1, "mesh4"), (1, "one")), True),
    (_cells((2, "mesh2"), (1, "one")), True),
    (_cells((4, "mesh4-model"), (1, "one")), True),
    (_cells((4, "mesh4"), (4, "mesh4"), (1, "one")), True),
    (_cells((4, "mesh4"), (4, "mesh4"), (1, "one"), (1, "one")), False),
], ids=["one-chip", "mesh4-beside-one", "mesh4-alone", "four-without-mesh",
        "one-with-mesh4", "two-chips", "axis-not-data",
        "two-of-three-take-four", "two-of-four-take-four"])
def test_the_chip_rules(workloads, broken):
    configs = {"one": {}, "mesh4": {"mesh": {"devices": 4, "axis": "data"}},
               "mesh2": {"mesh": {"devices": 2, "axis": "data"}},
               "mesh4-model": {"mesh": {"devices": 4, "axis": "model"}}}
    assert bool(chip_rule_errors(workloads, configs)) == broken
