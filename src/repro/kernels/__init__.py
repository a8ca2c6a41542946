"""Pallas kernel layer: the fill hot-spot the paper optimizes with a custom
CUDA kernel (vegas_fill.py + ops.py + ref.py) plus the execution-mode
policy shared by every caller.

:func:`resolve_interpret` picks the execution mode.  ``interpret=None`` (the
default everywhere) autodetects: compiled by Mosaic on TPU, the Pallas
interpreter elsewhere.  Explicit True/False is honored but logged loudly —
the historical failure mode was ``interpret=True`` silently running the
(orders-of-magnitude slower) interpreter on a real TPU.
"""

from __future__ import annotations

import functools
import logging

import jax

log = logging.getLogger("repro.kernels")


def device_kind() -> str:
    """The detected accelerator model (``'cpu'`` / ``'TPU v5 lite'`` ...)
    — the key cost-table classes are qualified by, so numbers from
    different silicon never mix."""
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


@functools.lru_cache(maxsize=None)
def _announce(platform: str, mode: str, source: str) -> None:
    msg = (f"Pallas fill mode: {mode.upper()} on platform={platform} "
           f"({source})")
    if mode == "interpret" and platform == "tpu":
        log.warning("%s — the interpreter is orders of magnitude slower "
                    "than compiled Mosaic; pass interpret=None to "
                    "autodetect", msg)
    elif mode == "compiled" and platform != "tpu":
        log.warning("%s — compiled Mosaic lowering is only supported on "
                    "TPU; this will likely fail to lower", msg)
    else:
        log.info("%s", msg)


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the tri-state ``interpret`` flag to a concrete bool, logging
    the choice once per (platform, flag) combination."""
    platform = jax.default_backend()
    if interpret is None:
        chosen = platform != "tpu"
        _announce(platform, "interpret" if chosen else "compiled",
                  "autodetected, interpret=None")
    else:
        chosen = bool(interpret)
        _announce(platform, "interpret" if chosen else "compiled",
                  f"explicit interpret={chosen}")
    return chosen
