"""Whole runs of each driver on the CPU at a small size, and runs with
the timed path broken underneath, which have to come out not correct."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import run
from harness import cells

CELLS = [w["name"] for w in cells.benchmark()["workloads"]]


def _run(capsys, small, cell, seconds=2.0, wrap=None, trace=0):
    config, traffic = small(cell)
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 17),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_tpu=False, config_override=config,
                  traffic_override=traffic, wrap_pipeline=wrap)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell", CELLS)
def test_a_whole_run_is_correct(capsys, small_cell, cell):
    result, err = _run(capsys, small_cell, cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    wanted = {m["name"] for m in cells.metrics_of(cell, "end_to_end")}
    assert set(result["metrics"]) == wanted
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # Every number compared ends standard error beside its limit.
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)


class _Broken:
    """The program's pipeline with one fault planted under ``run_epoch``."""

    def __init__(self, pipe, fault):
        self._pipe, self._fault, self._prev = pipe, fault, None

    def __getattr__(self, name):
        return getattr(self._pipe, name)

    def run_epoch(self, state, key, values, strata, counts, budgets=None):
        import jax
        import jax.numpy as jnp

        if self._fault == "state_unchanged":
            before = jax.tree.map(jnp.copy, state)
        if self._fault == "half_the_batch":
            counts = np.array(counts, copy=True)
            counts[:, : counts.shape[1] // 2] = 0
        new, wa = self._pipe.run_epoch(state, key, values, strata, counts,
                                       budgets)
        if self._fault == "state_unchanged":
            return before, wa
        if self._fault.startswith("answer:"):
            o, w, _ = self._pipe.query_layout()[self._fault[7:]]
            ans = jnp.asarray(wa.answers)
            ans = ans.at[:, o:o + w].multiply(1.5).at[:, o:o + w].add(3.0)
            wa = wa._replace(answers=ans)
        if self._fault == "previous_epoch":
            prev, self._prev = self._prev, wa
            if prev is not None:
                wa = wa._replace(answers=prev.answers, bounds=prev.bounds,
                                 n_sampled=prev.n_sampled)
        return new, wa


@pytest.mark.parametrize("fault", [
    "state_unchanged", "half_the_batch", "answer:requests",
    "answer:latency_total_ms", "answer:latency_mean_ms",
    "answer:latency_q_ms", "answer:latency_q_recent_ms",
    "answer:hot_latency_keys", "previous_epoch"])
def test_a_broken_timed_path_is_not_correct(capsys, small_cell, fault):
    result, _ = _run(capsys, small_cell, "paper-testbed.bulk",
                     wrap=lambda pipe: _Broken(pipe, fault))
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("fault", ["half_the_batch", "previous_epoch"])
def test_the_served_path_with_a_fault_is_not_correct(capsys, small_cell,
                                                     fault):
    result, _ = _run(capsys, small_cell, "paper-testbed.paced-10hz",
                     wrap=lambda pipe: _Broken(pipe, fault))
    assert not result["correct"], result["checks"]


def test_without_a_tpu_it_exits_nonzero_and_prints_nothing(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "Nothing was run" in out.err


def test_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout == ""


# A run of the bulk cell's small configuration stated on a 4-chip mesh,
# in a process of its own with ``devices`` CPU devices. ``api.compile``
# records the mesh it is given and returns the one-chip pipeline.
_MESH_RUN = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
    from conftest import small
    from repro import api
    import run

    seen, real = [], api.compile

    def compile(spec, *, mesh=None, **kw):
        seen.append(None if mesh is None else
                    [len(mesh.devices.flat), list(mesh.axis_names)])
        return real(spec)

    api.compile = compile
    config, traffic = small("paper-testbed.bulk")
    config["mesh"] = {{"devices": 4, "axis": "data"}}
    rc = run.main(["--workload", "paper-testbed.bulk", "--seed",
                   str(2 ** 31 + 29), "--seconds", "2", "--trace", "0"],
                  require_tpu=False, config_override=config,
                  traffic_override=traffic)
    print(json.dumps({{"rc": rc, "meshes": seen}}))
""")


def _mesh_run(devices: int):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_"
               f"device_count={devices}")
    code = _MESH_RUN.format(tests=str(cells.BENCH / "tests"),
                            bench=str(cells.BENCH),
                            src=str(cells.ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip().splitlines(), p.stderr


def test_a_configuration_on_four_chips_compiles_onto_a_four_chip_mesh():
    lines, err = _mesh_run(4)
    done = json.loads(lines[-1])
    assert done == {"rc": 0, "meshes": [[4, ["data"]]]}, err[-3000:]
    result = json.loads(lines[-2])
    assert result["correct"], result["checks"]
    assert result["device"]["count"] == 4
    per_chip = result["device"]["memory_peak_bytes_per_chip"]
    assert len(per_chip) == 4
    assert result["device"]["memory_peak_bytes"] == max(per_chip)


def test_a_four_chip_configuration_on_one_device_runs_nothing():
    lines, err = _mesh_run(1)
    assert lines == [json.dumps({"rc": 2, "meshes": []})]
    assert "needs 4 TPU chip(s)" in err and "Nothing was run" in err


def test_a_configuration_without_a_mesh_is_on_one_chip():
    _, config, _ = cells.cell("paper-testbed.bulk")
    assert "mesh" not in config
    assert cells.mesh(config) is None and cells.chips(config) == 1
    with pytest.raises(ValueError, match="axis"):
        cells.mesh({"mesh": {"devices": 4, "axis": "model"}})
