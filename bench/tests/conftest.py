"""CPU tests of the benchmark's harness, at a size a test run holds.

They put ``bench/`` and the program's ``src/`` on the path, force the
CPU, and shrink each configuration: a tenth of the rate or less, 8-tick
epochs, a 16-tick ring."""
from __future__ import annotations

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


def small(cell: str):
    """``(config, traffic)`` of ``cell`` cut to a CPU test's size."""
    from harness import cells

    _, config, traffic = cells.cell(cell)
    config, traffic = dict(config), dict(traffic)
    config.pop("capacity", None)
    traffic["epoch_ticks"] = 8
    if "items_per_s" in traffic:
        traffic["items_per_s"] = 20_000
    else:
        config["substreams"] = [dict(s, rate=200)
                                for s in config["substreams"]]
    traffic["ring_ticks"] = 16
    return config, traffic


@pytest.fixture
def small_cell():
    return small
