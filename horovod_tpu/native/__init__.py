"""Native (C++) control-plane core + pure-Python twin.

Layout (parity: the reference's C++ core horovod/common/* built by
CMake into the framework .so; see SURVEY.md §2.1):

- ``src/``        C++17 sources → ``libhvt_core.so`` (built on demand)
- ``core.py``     ctypes bindings (parity: basics.py ctypes loading)
- ``wire.py``     Python mirror of the coordination wire format
- ``fallback.py`` pure-Python controller with identical bytes/semantics

``make_controller`` picks the native implementation when a toolchain is
available, else the fallback — both speak the same wire format, so
mixed fleets coordinate fine.  ``controller_kind()`` says which one a
process gets, and why.
"""

from __future__ import annotations

import logging
import os

from . import core, fallback, wire

logger = logging.getLogger("horovod_tpu")


def native_available() -> bool:
    return core.available()


def controller_kind() -> str:
    """Which eager controller ``make_controller`` builds here:
    ``"native"`` or ``"python (<why not native>)"``."""
    if os.environ.get("HVTPU_FORCE_PY_CONTROLLER"):
        return "python (HVTPU_FORCE_PY_CONTROLLER is set)"
    if core.available():
        return "native"
    return f"python ({core.unavailable_reason})"


def make_controller(rank: int, size: int, fusion_threshold: int,
                    cache_capacity: int = 1024, stall_warn_s: float = 60.0,
                    stall_abort_s: float = 0.0,
                    resync_every: int = None):
    """Controller factory: native if buildable, else Python fallback.
    ``HVTPU_FORCE_PY_CONTROLLER=1`` forces the fallback (tests use this
    to cross-check both).  ``resync_every`` is the steady-state bypass
    cadence (every Nth all-cache-hit cycle sends a full resync blob; 0
    disables bypass); defaults to ``HVTPU_CACHE_RESYNC_EVERY`` or 64.
    Every rank must agree on the value — it shapes the wire traffic
    pattern, not the decisions, so the launcher env is the natural
    distribution channel."""
    if resync_every is None:
        resync_every = int(os.environ.get("HVTPU_CACHE_RESYNC_EVERY", "64"))
    kind = controller_kind()
    if kind == "native":
        return core.NativeController(
            rank, size, fusion_threshold, cache_capacity,
            stall_warn_s, stall_abort_s, resync_every=resync_every,
        )
    if not os.environ.get("HVTPU_FORCE_PY_CONTROLLER"):
        logger.warning(
            "eager controller: native core unavailable, using the "
            "Python twin — %s", core.unavailable_reason)
    return fallback.PyController(
        rank, size, fusion_threshold, cache_capacity,
        stall_warn_s, stall_abort_s, resync_every=resync_every,
    )


__all__ = [
    "core", "fallback", "wire", "native_available", "controller_kind",
    "make_controller",
]
