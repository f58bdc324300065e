"""What one run saw, as handed to every per-layer metric's reader."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from benchmark.loop import Window
from benchmark.xplane import TraceReduction


@dataclasses.dataclass
class Observations:
    config: Dict[str, Any]            # the configuration's file
    traffic: Dict[str, Any]           # the traffic mix's file
    chips: int
    device_kind: str
    window: Window                    # the untraced window's host clock
    setup_s: float                    # process start -> first dispatch
    samples_per_step_per_chip: int
    train_flops_per_sample: int
    compile_s: float                  # backend compile during set-up
    cache_hits: int                   # persistent-cache hits during set-up
    gradient_bytes: int               # one gradient tree
    memory_peak_bytes: Optional[int]  # fullest chip, after the window
    compiled_text: Optional[str] = None       # the step program, optimised
    trace: Optional[TraceReduction] = None    # the traced sub-window
