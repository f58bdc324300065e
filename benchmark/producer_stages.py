"""The loader's producer thread over the untraced window, from the
program's own record of it.

``horovod_tpu.data.loader`` reads the clock at the four boundaries of
every batch its prefetch thread queues (before the plan, after
``source.fetch``, after transform and ``device_put``, after the
``queue.put`` that took the batch) and sums the three stages between
them into ``hvtpu_data_fetch_seconds``, ``hvtpu_data_transform_seconds``
and ``hvtpu_data_backpressure_seconds``.  Those histograms hold the
process's totals: set-up, where the producer is parked for seconds, and
the traced window, where the profiler slows the copies to the chips
(PERF.md section 6), are in them.  ``recent_stages`` gives the same
readings batch by batch on ``time.perf_counter()``, the clock of
``Window``, so the batches queued wholly inside the untraced window can
be taken alone.  A snapshot of the histograms on either side of the
window would do as well and is what ISSUE 24 asked for; it needs
``run.py`` to take it, which this PR could not edit (PERF.md section 7).
"""

from __future__ import annotations

from typing import Dict, Optional

COUNTERS = ("hvtpu_data_fetch_seconds", "hvtpu_data_transform_seconds",
            "hvtpu_data_backpressure_seconds")


def over_window(obs) -> Optional[Dict[str, Dict[str, float]]]:
    """``{counter: {"sum": seconds, "count": batches}}`` over the
    untraced window, as a difference of two snapshots of the three
    histograms would read; None where the program keeps no such record
    (a commit before the counters) or queued no batch in the window."""
    try:
        from horovod_tpu.data.loader import recent_stages
    except ImportError:
        return None
    first, last = obs.window.first_dispatch, obs.window.last_completion
    stages = [s for s in recent_stages() if s[0] >= first and s[3] <= last]
    if not stages:
        return None
    return {name: {"sum": sum(s[k + 1] - s[k] for s in stages),
                   "count": len(stages)}
            for k, name in enumerate(COUNTERS)}


def ms_per_batch(obs, counter: str) -> Optional[float]:
    seen = over_window(obs)
    return seen and 1e3 * seen[counter]["sum"] / seen[counter]["count"]
