"""Which implementation a kernel of the package takes: the Pallas kernel
or its XLA twin, decided by what the process can observe.

Every kernel reads it from here (``ops/pallas_ops.py``, ``ops/ring.py``,
``parallel/moe.py``, ``models/attention.py``, ``models/kimi_linear.py``),
so tests steer them all through ``on_tpu`` and the environment.
"""

from __future__ import annotations

import os
from typing import Tuple

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_mode() -> Tuple[bool, bool]:
    """(use_pallas, interpret).  HVTPU_PALLAS=0 disables the kernels
    entirely; HVTPU_PALLAS_INTERPRET=1 forces the Pallas path in
    interpreter mode so CPU tests execute the real kernel bodies."""
    if os.environ.get("HVTPU_PALLAS", "1") == "0":
        return False, False
    if os.environ.get("HVTPU_PALLAS_INTERPRET", "0") == "1":
        return True, True
    return on_tpu(), False
