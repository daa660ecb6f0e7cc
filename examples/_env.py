"""Example-CLI environment helper. Import this FIRST in every example CLI.

``KFAC_FORCE_PLATFORM=cpu[:N]`` is an explicit request, made by tests, to
run on the CPU backend (optionally with N virtual host devices). Nothing
sets it by default and nothing falls back to it: without it the program
runs on whatever JAX finds, which on the machine with the chip is the TPU.

Also places the persistent compile cache
(``compile_cache.enable_persistent_cache``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_force = os.environ.get("KFAC_FORCE_PLATFORM")
if _force:
    plat, _, n = _force.partition(":")
    if plat != "cpu":
        raise ValueError(f"KFAC_FORCE_PLATFORM only supports cpu[:N], got {_force!r}")
    from kfac_pytorch_tpu.platform_override import force_cpu_devices

    if not force_cpu_devices(int(n) if n else None):
        raise RuntimeError(
            "could not force the CPU platform — a JAX backend was already "
            "instantiated before examples/_env.py was imported"
        )

from kfac_pytorch_tpu.compile_cache import enable_persistent_cache

enable_persistent_cache()
