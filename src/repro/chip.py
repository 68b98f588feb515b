"""What a process that drives the chip does first: find the TPU, and keep
compiled programs in a persistent cache.

Neither runs at ``import repro``: tests and CPU tools never touch the
cache or demand an accelerator.  ``chip_smoke.py`` and
``benchmarks/run.py`` call ``enable_compile_cache`` before their first
compile.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout (listed in ``.gitignore``).  The path is
#: part of each entry's key, so it must never move between runs.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def require_tpu():
    """The first JAX device, which must be a TPU.

    A measurement or smoke run that finds no TPU stops here with a message
    naming the platform it found; it never carries on on the CPU.
    """
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is on platform "
                         f"{dev.platform!r} ({dev.device_kind}); this run "
                         f"needs a TPU and does not fall back")
    return dev
