"""Process-level JAX set-up shared by the entry points.

* :func:`ensure_virtual_cpu_devices` — the one copy of the order-
  sensitive virtual CPU-mesh recipe (tests, the driver's multichip
  dryrun): the host-platform device-count flag and the platform pin
  only take effect BEFORE the first JAX backend query.
* :func:`enable_compile_cache` — JAX's persistent compilation cache at
  a fixed path, so every process of a chip run (and every later run on
  the same checkout) reuses compiled executables.
"""

from __future__ import annotations

import os
from pathlib import Path

_provisioned: int | None = None

#: Default persistent compile-cache directory (gitignored).  A fixed
#: path: the directory is part of the cache key, so a temp name would
#: never hit.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing is set here; otherwise the cache
    goes to :data:`REPO_CACHE_DIR`.  Called by the entry points
    (``chip_smoke.py``, ``bench.py``, the CLI and the server), never at
    package import."""
    from mpitest_tpu.utils import knobs

    env_dir = knobs.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def host_fingerprint() -> str:
    """Provenance identifier for host-dependent pinned numbers (ADVICE
    round 5): CPU model + core count — what actually determines the
    native backend's throughput.  Hostnames are useless here (container
    names are random); a CPU fingerprint survives container rebuilds on
    the same machine class and differs where the numbers would differ.
    Shared by bench.py (CANONICAL_NATIVE_MKEYS gate) and the report
    CLI's baseline comparison."""
    fields = {}
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if ":" not in line:
                    continue
                k, v = line.split(":", 1)
                k, v = k.strip(), v.strip()
                # first processor block only; VMs often report model
                # name "unknown", so keep vendor/family/model numbers too
                if k in ("vendor_id", "cpu family", "model", "model name"
                         ) and k not in fields:
                    fields[k] = v
    except OSError:
        pass
    name = fields.get("model name", "")
    if name and name != "unknown":
        cpu = name
    elif fields:
        cpu = "-".join(filter(None, (fields.get("vendor_id"),
                                     fields.get("cpu family"),
                                     fields.get("model"))))
    else:
        import platform as _platform

        cpu = _platform.processor() or _platform.machine() or "unknown-cpu"
    return f"{cpu}/{os.cpu_count()}c"


def _backend_initialized() -> bool:
    # jax.devices() would *create* the backend; peek at the registry
    # instead (private, but the only non-initializing probe there is —
    # pinned-version image, exercised by tests).
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def ensure_virtual_cpu_devices(n_devices: int) -> None:
    """Pin this process to an ``n_devices``-device virtual CPU platform.

    Must be called before the first backend query.  If JAX was already
    initialized: a no-op when enough devices exist (or this helper
    already provisioned at least as many), otherwise an actionable
    error — never a silent platform hijack of a live TPU process.
    """
    global _provisioned
    import jax

    if _provisioned is not None or _backend_initialized():
        if (_provisioned or 0) >= n_devices or len(jax.devices()) >= n_devices:
            return
        raise RuntimeError(
            f"need {n_devices} devices but JAX is already initialized "
            f"({_provisioned or len(jax.devices())} available); call "
            "ensure_virtual_cpu_devices() before any backend query, or "
            "run in a fresh process with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices}"
        )
    from mpitest_tpu.utils import knobs

    os.environ["XLA_FLAGS"] = (
        knobs.get("XLA_FLAGS")
        + f" --xla_force_host_platform_device_count={n_devices}"
    )
    jax.config.update("jax_platforms", "cpu")
    _provisioned = n_devices
