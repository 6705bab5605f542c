"""Where this process's JAX runs, and where it keeps compiled code.

The cluster -> chip map.  A TPU chip belongs to one process at a time,
so a deployment names ONE chip owner per chip:

- `python -m seaweedfs_tpu server` (master + volume server in one
  process), or one designated `volume` process, owns the chip: it runs
  with JAX's default platform, resolves the Pallas coder
  (ops/erasure.default_backend) and says so in its start-up log line.
- Every other process on that host — further volume servers, a separate
  master, filers, gateways, the shell — is started with
  `JAX_PLATFORMS=cpu`, resolves the native coder and never initialises a
  TPU backend.  `ec.encode -batch` / `ec.rebuild -batch` (shell process)
  and the repair daemon's EC leg (master process) run the device mesh in
  the process that invokes them, so THAT process must be the chip owner
  when they are to run on the chip.

Nothing here falls back: device discovery errors propagate to the
caller, and a process that was meant to own a chip but cannot get it
fails instead of quietly coding on the CPU.
"""

from __future__ import annotations

import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root() -> str:
    """Directory of the one on-disk cache the device path keeps, JAX's
    persistent compilation cache.  Placed
    from outside with JAX_COMPILATION_CACHE_DIR; otherwise a fixed,
    git-ignored path in the checkout (the path is part of JAX's cache
    key, so it must not move between runs)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `cache_root()`.
    Entry points call this before first JAX use.  With
    JAX_COMPILATION_CACHE_DIR set this sets nothing — JAX reads the
    variable itself.  Never imports JAX (a 2 s import no CLI client
    command should pay): before the import it leaves the default in the
    environment, where JAX and every child process find it."""
    root = cache_root()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax = sys.modules.get("jax")
        if jax is None:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = root
        else:
            jax.config.update("jax_compilation_cache_dir", root)
    return root


def force_cpu(device_count: int = 8) -> None:
    """Pin this process and its children to `device_count` virtual CPU
    devices (the tier-1 test mesh).  Call before the first JAX
    computation; the device count is fixed once a backend exists."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{device_count}").strip()
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def device_summary() -> dict:
    """{"platform", "device_kind", "count"} of the devices this process
    computes on, as JAX reports them.  Initialises the backend — on a
    TPU host that CLAIMS the chip — and raises if it cannot."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}


def platform() -> str:
    return device_summary()["platform"]
