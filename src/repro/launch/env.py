"""Environment / launch-profile helper: one place for the process-level JAX
environment knobs the launchers used to set ad hoc via ``os.environ``.

Four idioms (see SNIPPETS.md for their upstream forms):

  * **precision** — :func:`enable_x64` honors the ``JAX_ENABLE_X64``
    environment variable when no explicit flag is given (f64 accumulation
    runs, e.g. ``--backend ref`` with ``dtype='float64'``);
  * **platform** — :func:`set_platform` pins the JAX platform
    (cpu/gpu/tpu) before the backend initializes;
  * **host devices** — :func:`set_host_device_count` forces N host CPU
    devices via ``XLA_FLAGS`` (the multi-device tests' idiom) — it MUST run
    before jax first initializes its backends;
  * **compile cache** — :func:`use_compile_cache` turns on JAX's persistent
    compilation cache for the CLIs (never on ``import repro``).

Everything importing jax does so lazily inside the function, so this module
can be imported (and ``set_host_device_count`` called) before jax is — the
ordering the distributed test worker needs.
"""

from __future__ import annotations

import os
import re
import warnings
from pathlib import Path

#: Where compiled programs are cached when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed directory of the checkout, so a later run finds them again
#: (the path is part of the cache key; gitignored).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_TRUTHY = ("1", "true", "yes", "on")


def _jax_initialized() -> bool:
    """True once jax has committed to its backends (after which platform /
    device-count changes are silently ineffective)."""
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        return jax._src.xla_bridge._backends != {}  # noqa: SLF001
    except Exception:
        return False


def enable_x64(enable: bool | None = None) -> bool:
    """Enable (or disable) 64-bit JAX types.  ``None`` reads the standard
    ``JAX_ENABLE_X64`` environment variable (unset -> False).  Safe to call
    after jax import; returns the value applied."""
    if enable is None:
        enable = os.environ.get("JAX_ENABLE_X64", "").lower() in _TRUTHY
    import jax
    jax.config.update("jax_enable_x64", bool(enable))
    return bool(enable)


def set_platform(platform: str | None = None) -> str | None:
    """Pin the JAX platform (``'cpu'``/``'gpu'``/``'tpu'``).  ``None``
    reads ``JAX_PLATFORMS`` / ``JAX_PLATFORM_NAME`` and applies nothing if
    both are unset.  Returns the platform applied, or None."""
    if platform is None:
        platform = (os.environ.get("JAX_PLATFORMS")
                    or os.environ.get("JAX_PLATFORM_NAME"))
        if not platform:
            return None
    if _jax_initialized():
        warnings.warn(f"set_platform({platform!r}) after jax initialized "
                      f"its backends — the platform cannot change anymore",
                      RuntimeWarning, stacklevel=2)
        return platform
    import jax
    jax.config.update("jax_platforms", platform)
    return platform


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is left to JAX, which reads it
    itself: nothing is set in code.  Otherwise the cache goes to
    :data:`CACHE_DIR`.  Call before the first compilation — JAX decides
    once per process whether a cache is in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def set_host_device_count(n: int) -> int:
    """Force ``n`` host CPU devices via
    ``--xla_force_host_platform_device_count`` (the multi-device test /
    example idiom).  Must run BEFORE jax initializes its backends; replaces
    any prior count in ``XLA_FLAGS`` instead of appending duplicates."""
    if _jax_initialized():
        warnings.warn(f"set_host_device_count({n}) after jax initialized "
                      f"its backends — the device count cannot change "
                      f"anymore", RuntimeWarning, stacklevel=2)
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\S+", "",
                   flags).strip()
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())
    return n


def add_env_args(ap) -> None:
    """The shared environment flags (integrate / sweep / serve CLIs)."""
    ap.add_argument("--x64", action="store_true",
                    help="enable 64-bit JAX types (also honored from "
                         "JAX_ENABLE_X64=1)")
    ap.add_argument("--platform", choices=["cpu", "gpu", "tpu"],
                    default=None,
                    help="pin the JAX platform (must act before the first "
                         "computation; default: JAX_PLATFORMS/autodetect)")
    ap.add_argument("--host-devices", type=int, default=None, metavar="N",
                    help="force N host CPU devices (XLA_FLAGS; must act "
                         "before jax backend init)")


def apply_env_args(args) -> None:
    """Apply the `add_env_args` flags in dependency order: device count and
    platform first (backend-init-sensitive), x64 last (always safe)."""
    if getattr(args, "host_devices", None):
        set_host_device_count(args.host_devices)
    if getattr(args, "platform", None):
        set_platform(args.platform)
    if getattr(args, "x64", False) or "JAX_ENABLE_X64" in os.environ:
        enable_x64(True if getattr(args, "x64", False) else None)
