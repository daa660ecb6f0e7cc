"""Point JAX at the CPU platform with N virtual devices.

Sets ``JAX_PLATFORMS=cpu`` and the ``xla_force_host_platform_device_count``
flag, then checks that the override took: backends instantiate lazily, so it
works any time before first device use. Used by tests/conftest.py,
examples/_env.py and ``__graft_entry__.dryrun_multichip`` — the multi-device
collective/sharding paths (pmean/psum/shard_map) run on virtual CPU devices
with real SPMD semantics.

jax itself is only imported inside the function so the env mutations land
first.
"""

from __future__ import annotations

import os
import re
from typing import Optional

_COUNT_OPT = "--xla_force_host_platform_device_count"


def force_cpu_devices(n: Optional[int] = None) -> bool:
    """Point JAX at the CPU platform with ``n`` virtual devices.

    Rewrites any existing ``xla_force_host_platform_device_count`` flag
    (rather than keeping a stale count) and overrides the already-set
    ``jax_platforms`` config. Returns True iff the override took effect —
    False means some backend was already instantiated (e.g. ``jax.devices()``
    ran earlier in this process), which locks the platform in; callers should
    treat that as an error if they need the virtual mesh.
    """
    if n:
        flags = os.environ.get("XLA_FLAGS", "")
        opt = f"{_COUNT_OPT}={n}"
        if _COUNT_OPT in flags:
            flags = re.sub(rf"{_COUNT_OPT}=\d+", opt, flags)
        else:
            flags = f"{flags} {opt}".strip()
        os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # Does not raise even if a backend is live (verified on jax 0.9.0) — the
    # post-update device check below is the real detection.
    jax.config.update("jax_platforms", "cpu")
    return jax.devices()[0].platform == "cpu" and (
        n is None or jax.device_count() >= n
    )
