"""The one traffic generator: every item a cell offers, made from the
seed on the host before the measured window.

A configuration names its sub-streams (distribution, parameters and a
rate); a traffic mix may set the rate in items per second instead.
Source ``i`` feeds level-0 node ``i // (num_sources / n0)``,
the testbed wiring. Items are generated once into a ring of
``ring_ticks`` ticks that the drivers replay in order, so set-up does
not grow with the window, and the reference is computed per ring tick.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Ring:
    """``ticks[t][i]`` is the ``(values f32, strata i32)`` pair source
    ``i`` delivers in ring tick ``t``."""

    ticks: list
    num_sources: int
    n_nodes: int

    def __len__(self) -> int:
        return len(self.ticks)

    def node_of(self, source: int) -> int:
        return source // (self.num_sources // self.n_nodes)

    def tick_values(self, t: int) -> np.ndarray:
        """All items of ring tick ``t``, in source order."""
        return np.concatenate([v for v, _ in self.ticks[t]])

    def items(self, t: int) -> int:
        return sum(v.size for v, _ in self.ticks[t])

    def packed(self, t0: int, n: int, width: int):
        """Ring ticks ``t0 .. t0+n-1`` as ``run_epoch`` ingest:
        ``values f32[n, nodes, width]``, ``strata i32[..]``,
        ``counts i32[n, nodes]``. Raises if a node overflows ``width``
        (the deployment's capacity must hold every offered item)."""
        values = np.zeros((n, self.n_nodes, width), np.float32)
        strata = np.zeros((n, self.n_nodes, width), np.int32)
        counts = np.zeros((n, self.n_nodes), np.int32)
        for k in range(n):
            for i, (v, s) in enumerate(self.ticks[t0 + k]):
                node = self.node_of(i)
                fill = int(counts[k, node])
                if fill + v.size > width:
                    raise ValueError(f"ring tick {t0 + k}: node {node} "
                                     f"holds more than {width} items")
                values[k, node, fill:fill + v.size] = v
                strata[k, node, fill:fill + v.size] = s
                counts[k, node] = fill + v.size
        return values, strata, counts


def tick_rates(config: dict, traffic: dict) -> np.ndarray:
    """Mean items per tick per source of each sub-stream. A traffic mix
    that gives ``items_per_s`` (and its ``tick_seconds``) spreads that
    rate over the sources and the sub-streams in the ratio of their
    configured rates; otherwise the configured rates are per tick
    already."""
    rates = np.asarray([s["rate"] for s in config["substreams"]], np.float64)
    if "items_per_s" in traffic:
        per_tick = traffic["items_per_s"] * traffic["tick_seconds"]
        rates = rates / rates.sum() * per_tick / config["num_sources"]
    return rates


def _draw(rng, dist: str, params, n: int) -> np.ndarray:
    if dist == "gaussian":
        return rng.normal(params[0], params[1], n)
    if dist == "lognormal":
        return rng.lognormal(params[0], params[1], n)
    raise ValueError(f"unknown distribution {dist!r}")


def make_ring(config: dict, traffic: dict, seed: int, ring_ticks: int
              ) -> Ring:
    """The cell's ring of ticks from ``seed``: the same seed gives the
    same items. Each source draws from its own stream of the seed."""
    rates = tick_rates(config, traffic)
    specs = config["substreams"]
    n_src = int(config["num_sources"])
    rngs = [np.random.default_rng([int(seed), i]) for i in range(n_src)]
    ticks = []
    for t in range(ring_ticks):
        row = []
        for rng in rngs:
            counts = rng.poisson(rates)
            vals = [_draw(rng, sp["dist"], sp["params"], int(c))
                    for sp, c in zip(specs, counts)]
            row.append((np.concatenate(vals).astype(np.float32),
                        np.repeat(np.arange(len(specs), dtype=np.int32),
                                  counts)))
        ticks.append(row)
    return Ring(ticks, n_src, int(config["fanin"][0]))
