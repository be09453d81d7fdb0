"""The program's one door to JAX.

Every lazy ``import jax`` in the package goes through
:func:`jax_modules`, so the persistent compilation cache is placed
before the first program compiles -- whichever entry point got there
first (chip_smoke.py, benchmarks/run.py, an example, a fleet worker, a
direct use of ``ops/``).  The package itself still imports without JAX:
host plane processes never call this.

Also holds the per-device peak table the stats JSON's roofline estimate
divides by.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

def compile_cache_dir() -> str:
    """Directory of JAX's persistent compilation cache for this
    program: ``JAX_COMPILATION_CACHE_DIR`` when the environment places
    it, else ``<checkout>/.jax_cache``.  A fixed path on purpose: the
    path is part of what makes a later process find the entries."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_CHECKOUT, ".jax_cache")


@functools.lru_cache(maxsize=None)
def jax_modules():
    """``(jax, jax.numpy)``, with the compilation cache placed on the
    first call.  When the environment names the directory JAX has read
    it already and nothing is set here.  A process held to the CPU
    backend (the tests) gets no cache from this program: its programs
    must compile from source every time, and this jaxlib logs an error
    on every XLA:CPU cache hit."""
    import jax
    import jax.numpy as jnp
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            and jax.config.jax_platforms != "cpu":
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # default 1.0 s: most of the bucketed window programs compile
    # faster than that and would never be stored
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax, jnp


def open_tpu(min_devices: int = 1, out=None) -> dict:
    """For the entry points that must run on the chip (chip_smoke.py):
    load JAX, print what it found on ``out`` (stdout when
    None), and refuse anything but a TPU with ``min_devices`` chips --
    a chip that cannot be opened is an error, never a CPU run.  Returns
    the device as JAX reports it."""
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    jax, _ = jax_modules()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={device['count']}", file=out)
    print(f"compile cache: {compile_cache_dir()}", file=out)
    if jax.default_backend() != "tpu":
        raise SystemExit(f"backend is {jax.default_backend()!r}, not 'tpu': "
                         "device numbers come from a chip or not at all")
    if device["count"] < min_devices:
        raise SystemExit(f"{device['count']} device(s), need {min_devices}")
    return device
