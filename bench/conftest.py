"""The harness tests run every cell in process, the 4-chip ones too: the
CPU backend is given four devices before JAX starts (a process's device
count is fixed when its backend starts)."""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        filter(None, [os.environ.get("XLA_FLAGS"), f"{_FLAG}=4"]))
