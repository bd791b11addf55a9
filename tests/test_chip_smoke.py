"""``chip_smoke.py`` rehearsed on the host: its refusal without a TPU, and
each of its phases at a small size with the Pallas kernels interpreted,
so a chip run spends its time on the chip and not on a broken check."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=600, cwd=cwd, env=env)


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_without_a_tpu(tmp_path, alone):
    """Without an accelerator — and in a directory that holds the script
    and nothing else of the repo — it exits non-zero and prints no
    result."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    out = _run([script], cwd=os.path.dirname(script),
               env_extra={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_max_misses_is_the_binomial_tail(smoke):
    """The coverage floor: the smallest miss count whose binomial upper
    tail under a calibrated ±2σ bound is at most COVERAGE_ALPHA."""
    import math

    p = smoke.NOMINAL_MISS
    for n, want in ((16, 4), (192, 19)):
        k = smoke.max_misses(n)
        assert k == want
        tail = lambda k: 1.0 - sum(math.comb(n, i) * p ** i
                                   * (1 - p) ** (n - i)
                                   for i in range(k + 1))
        assert tail(k) <= smoke.COVERAGE_ALPHA < tail(k - 1)


def test_kernel_phase_matches_oracles_interpreted(smoke):
    """Every kernel is compared with its oracle (a disagreement raises);
    lookups, masks and compactions agree bit-for-bit."""
    out = smoke.kernel_phase(2000, seed=0, fused_cap=1024, interpret=True)
    assert set(out) == {"default_f32_dot_exact", "stratified_stats",
                        "sample_mask", "cms_update", "quantile_compact",
                        "fused_level_tick", "fused_select"}
    for name in ("sample_mask", "quantile_compact", "fused_level_tick",
                 "fused_select"):
        assert out[name]["exact"], name


def test_main_path_phase_small(smoke):
    out = smoke.main_path_phase(seed=0, rate=200, epochs=2, epoch_ticks=8,
                                expect_kernels=False)
    assert out["windows"] == 16
    assert out["staged_items"] == sum(out["items_per_epoch"])
    for name in ("latency_total_ms", "latency_mean_ms"):
        assert out["worst"][name]["windows"] == 16


def test_mesh_phase_on_four_host_devices():
    """The ``--chips 4`` phase on four forced host devices (a child
    process: the device count is fixed when JAX starts)."""
    code = (f"import sys, json; sys.path.insert(0, {ROOT!r}); "
            "import chip_smoke as C; "
            "r = C.mesh_phase(seed=0, rate=200, ticks=4, "
            "expect_kernels=False); "
            "print(json.dumps(r['runs'], default=str))")
    out = _run(["-c", code], cwd=ROOT, env_extra={
        "PYTHONPATH": os.path.join(ROOT, "src"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-3000:]
    runs = json.loads(out.stdout.strip().splitlines()[-1])
    assert runs["4"]["shard_devices"] == [4]
    assert runs["1"]["shard_devices"] == [1]
