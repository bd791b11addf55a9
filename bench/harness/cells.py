"""Cells, configurations and traffic mixes, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic; their
files are ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``. Per-layer metric readers are
``bench/layers/<metric>.py`` and kernel cost functions
``bench/costs/<kernel>.py``; both are loaded from their path, so a
metric name may hold dots. A configuration may state the chips its
deployment spans (``"mesh": {"devices": N, "axis": "data"}``); without
it the deployment is on one chip.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> tuple[dict, dict, dict]:
    """``(workload entry, config, traffic)`` of the named cell."""
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return (w, load_json(BENCH / "configs" / f"{w['config']}.json"),
                    load_json(BENCH / "traffic" / f"{w['traffic']}.json"))
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def metrics_of(cell_name: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
    those that list it, and those that list no cells."""
    return [m for m in benchmark()[section]
            if cell_name in m.get("workloads", [cell_name])]


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (``kind`` is ``layers`` or
    ``costs``)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def capacity(config: dict, rates_per_tick) -> int:
    """Level-0 buffer per node: the configuration's own number, else the
    deployment's provisioning rule (``launch.analytics.default_capacity``:
    ``capacity_slack`` x the mean items per node per tick, plus 256,
    rounded down to a multiple of 256, at least 1024)."""
    if "capacity" in config:
        return int(config["capacity"])
    per_node = (float(sum(rates_per_tick)) * config["num_sources"]
                / config["fanin"][0])
    return max(int(config["capacity_slack"] * per_node) + 256 & ~255, 1024)


def chips(config: dict) -> int:
    """Chips the deployment spans: its ``mesh.devices``, else 1."""
    return int(config.get("mesh", {}).get("devices", 1))


def mesh(config: dict):
    """The deployment's device mesh: None where the configuration states
    no ``mesh`` (one chip, the local pipeline), else the program's own
    one-axis ``data`` mesh over ``mesh.devices`` chips, on which
    ``api.compile`` lowers the spec to the SPMD pipeline (level-0 edge
    node d on chip d)."""
    layout = config.get("mesh")
    if layout is None:
        return None
    if layout.get("axis") != "data":
        raise ValueError(f"a configuration's mesh axis is 'data'; got "
                         f"{layout.get('axis')!r}")
    from repro.launch.analytics import make_data_mesh

    return make_data_mesh(int(layout["devices"]))


def pipeline_spec(config: dict, cap: int, seed: int):
    """The deployment as the program's ``PipelineSpec``: the tree, the
    sampler, and the configuration's queries as the one tenant."""
    from repro import api
    from repro.query.registry import QueryRegistry

    reg = QueryRegistry()
    for q in config["queries"]:
        q = dict(q)
        kind, name = q.pop("kind"), q.pop("name")
        getattr(reg, f"register_{kind}")(name, **q)
    return api.PipelineSpec(
        topology=api.TopologySpec(fanin=tuple(config["fanin"]),
                                  capacity=cap,
                                  num_strata=len(config["substreams"])),
        sampler=api.SamplerSpec(mode="whs",
                                backend=config["sampler_backend"],
                                allocation=config["allocation"],
                                fraction=config["fraction"]),
        tenants=(reg.as_tenant("dashboard"),),
        seed=int(seed) % (2 ** 31))
