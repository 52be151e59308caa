"""Host mesh construction + forced-device-count helpers.

Everything here is a function (never a module-level constant) and jax is
imported *inside* the functions: importing this module must touch no JAX
device state, because the whole point of ``forced_host_devices`` is to
set ``--xla_force_host_platform_device_count`` **before** jax first
initializes its backends.  Once jax has picked up the flag, the CPU
platform exposes N virtual devices — the mechanism the sharded serving
cluster uses to test multi-device execution paths on a plain CPU host
(see docs/serving.md for the recipe).
"""
from __future__ import annotations

import os
import sys
from typing import Mapping, Optional

_FLAG = "xla_force_host_platform_device_count"


def _with_forced_count(flags: str, n: int) -> str:
    """``flags`` with any existing forced-count flag replaced by ``n``."""
    kept = [f for f in flags.split() if not f.startswith(f"--{_FLAG}=")]
    kept.append(f"--{_FLAG}={n}")
    return " ".join(kept)


def forced_host_devices(n: int) -> int:
    """Make the CPU backend expose ``n`` virtual devices in THIS process.

    Patches ``XLA_FLAGS`` in the environment (replacing any existing
    forced-count flag).  The flag is only read when jax initializes, so
    this must run before the first ``import jax`` anywhere in the
    process; calling it after jax is already imported raises rather than
    silently doing nothing — a too-late call is exactly the bug this
    guard exists to surface.  Returns ``n`` for convenience::

        from repro.launch.mesh import forced_host_devices
        forced_host_devices(4)        # BEFORE any jax import
        import jax
        assert len(jax.devices()) == 4
    """
    if n < 1:
        raise ValueError(f"forced device count must be >= 1, got {n}")
    if "jax" in sys.modules:
        raise RuntimeError(
            f"forced_host_devices({n}) called after jax was imported — "
            f"XLA_FLAGS is only read at backend init, so the flag would "
            f"be ignored.  Set it before the first jax import (or launch "
            f"a fresh process with forced_device_env({n}))")
    os.environ["XLA_FLAGS"] = _with_forced_count(
        os.environ.get("XLA_FLAGS", ""), n)
    return n


def forced_device_env(n: int,
                      base: Optional[Mapping[str, str]] = None) -> dict:
    """Environment dict for a *subprocess* that should see ``n`` host
    devices: a copy of ``base`` (default ``os.environ``) with the forced
    count patched into ``XLA_FLAGS`` and ``JAX_PLATFORMS=cpu``.  The
    escape hatch when jax is already live in the current process — the
    child reads the flag at its own backend init.  The child is held to
    the CPU: the forced devices are host devices, and on a TPU host the
    parent may hold the chip, which a second process cannot open."""
    if n < 1:
        raise ValueError(f"forced device count must be >= 1, got {n}")
    env = dict(base if base is not None else os.environ)
    env["XLA_FLAGS"] = _with_forced_count(env.get("XLA_FLAGS", ""), n)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def make_host_mesh():
    """Whatever devices exist on this host, as a 1D 'data' mesh — the
    mesh the sharded engine path (``ual.engine.ShardedKernelEngine``)
    shard_maps the batch axis over."""
    import jax
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",))
