"""The measured loop: one optimizer step per dispatch, a fresh batch
each, ``fence_lag`` steps in flight.

After dispatching step k the host blocks on the loss of step
k - fence_lag and notes when that returns; it stops dispatching when
the time (or the step count) is up and then blocks on everything.  The
host's three calls into the layers below it — the loader, the jitted
step, the fence — are timed on the benchmark's own clock, and in a
traced window also written into the profiler's trace, so that device
idle gaps can be laid against them.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

SPANS = ("data_next", "dispatch", "fence_wait")
# what a host span is called inside the profiler's trace
TRACE_PREFIX = "bench:"


@dataclasses.dataclass
class Window:
    steps: int = 0
    first_dispatch: float = 0.0       # perf_counter seconds
    last_completion: float = 0.0
    losses: List[float] = dataclasses.field(default_factory=list)
    fence_returns: List[float] = dataclasses.field(default_factory=list)
    spans: Dict[str, List[Tuple[float, float]]] = dataclasses.field(
        default_factory=lambda: {name: [] for name in SPANS})
    last_batch: Optional[dict] = None

    @property
    def seconds(self) -> float:
        return self.last_completion - self.first_dispatch

    def step_intervals(self) -> List[float]:
        """Seconds between successive fence returns."""
        r = self.fence_returns
        return [b - a for a, b in zip(r, r[1:])]

    def span_share(self, name: str) -> float:
        """Share of the window the host spent inside that span."""
        return sum(b - a for a, b in self.spans[name]) / self.seconds


def run_window(job, state, *, fence_lag: int, seconds: float = None,
               max_steps: int = None, annotate: bool = False):
    """Drive ``job.step`` from ``state`` until ``seconds`` have passed
    or ``max_steps`` were dispatched; returns ``(state, Window)``."""
    import numpy as np

    if annotate:
        from jax.profiler import TraceAnnotation

        def traced(name):
            return TraceAnnotation(TRACE_PREFIX + name)
    else:
        def traced(name):
            return contextlib.nullcontext()

    win = Window()
    clock = time.perf_counter

    @contextlib.contextmanager
    def span(name):
        with traced(name):
            t0 = clock()
            try:
                yield
            finally:
                win.spans[name].append((t0, clock()))

    def fence(loss):
        with span("fence_wait"):
            value = float(np.asarray(loss))
        win.fence_returns.append(clock())
        win.losses.append(value)

    params, model_state, opt_state = state
    in_flight = collections.deque()
    step, batches = job.step, job.batches
    win.first_dispatch = start = clock()
    while True:
        if seconds is not None and clock() - start >= seconds:
            break
        if max_steps is not None and win.steps >= max_steps:
            break
        with span("data_next"):
            batch = next(batches)
        with span("dispatch"):
            params, model_state, opt_state, loss = step(
                params, model_state, opt_state, batch)
        win.steps += 1
        in_flight.append(loss)
        if len(in_flight) > fence_lag:
            fence(in_flight.popleft())
    while in_flight:
        fence(in_flight.popleft())
    win.last_completion = win.fence_returns[-1]
    win.last_batch = batch
    return (params, model_state, opt_state), win
