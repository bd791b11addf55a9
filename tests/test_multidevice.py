"""Multi-device semantics, validated on 8 forced host devices.

These tests run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process must keep the default 1-device backend), and check the two
properties that cannot be observed on one device:

  * ``spmd_local_then_root`` on a real 8-way "data" mesh produces an
    accurate, *replicated* root estimate (§III-E distributed execution);
  * the group-local MoE dispatch (G = #batch shards) stays numerically
    equivalent to the single-group path on the same inputs.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.tree import spmd_local_then_root
    from repro.core.types import IntervalBatch, StratumMeta

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    m, x = 8192, 4
    batch = IntervalBatch(
        value=jnp.asarray(rng.normal(100, 10, m), jnp.float32),
        stratum=jnp.asarray(rng.integers(0, x, m), jnp.int32),
        valid=jnp.ones((m,), bool),
        meta=StratumMeta.identity(x),
    )
    def f(key, b):
        s, mn = spmd_local_then_root(key, b, axis_name="data", num_strata=x,
                                     local_budget=256, root_budget=512)
        return s.estimate, s.variance, mn.estimate
    specs = IntervalBatch(P("data"), P("data"), P("data"), StratumMeta(P(), P()))
    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(), specs),
                               out_specs=(P(), P(), P())))
    est, var, mean = fn(jax.random.PRNGKey(0), batch)
    print(json.dumps({
        "est": float(est), "var": float(var), "mean": float(mean),
        "exact": float(np.asarray(batch.value).sum()),
        "n_dev": len(jax.devices()),
    }))
""")

_MOE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import registry
    from repro.launch.meshctx import use_mesh
    from repro.models import moe as MOE

    cfg = registry.get_config("qwen2-moe-a2.7b").reduced()
    key = jax.random.PRNGKey(0)
    p = MOE.moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)

    # capacity_factor=8 ⇒ per-(group,expert) capacity == tg·k, so NOTHING
    # can drop in either path: outputs must agree exactly (the paths may
    # only differ through per-group-vs-global capacity drop patterns).
    cf = 8.0
    y1, aux1 = MOE.moe_apply(p, cfg, x, capacity_factor=cf)   # no mesh → G=1
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    with use_mesh(mesh):                          # G=4 group-local dispatch
        y4, aux4 = jax.jit(
            lambda p, x: MOE.moe_apply(p, cfg, x, capacity_factor=cf))(p, x)
    print(json.dumps({
        "max_dev": float(jnp.max(jnp.abs(y1 - y4))),
        "scale": float(jnp.max(jnp.abs(y1))),
        "aux1": float(aux1), "aux4": float(aux4),
    }))
""")


def _run(script: str) -> dict:
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_spmd_hierarchy_eight_devices():
    r = _run(_SPMD_SCRIPT)
    assert r["n_dev"] == 8
    assert abs(r["est"] - r["exact"]) / r["exact"] < 0.05
    assert r["var"] >= 0
    assert abs(r["mean"] - 100.0) < 5.0


def test_moe_group_local_dispatch_matches_single_group():
    r = _run(_MOE_SCRIPT)
    # Zero drops by construction → the two dispatch layouts compute the
    # same math; only einsum reduction order may differ.
    assert r["max_dev"] < 1e-3 * max(r["scale"], 1.0), r
    assert abs(r["aux1"] - r["aux4"]) < 1e-5
