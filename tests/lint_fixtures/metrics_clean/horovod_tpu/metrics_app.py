"""metrics-catalog fixture (clean): registry and docs agree."""

from .registry import REGISTRY, counter, gauge

STEPS = counter("hvtpu_fixture_steps_total", "Completed steps.")
DEPTH = gauge("hvtpu_fixture_queue_depth", "Pending items.")

# Registry-attribute registration with buckets and a multi-line help
# string — the obs/stepprof.py shape (PR 12).
EXPOSED = REGISTRY.histogram(
    "hvtpu_fixture_exposed_seconds",
    "Exposed (not overlapped) time per step; "
    "host upper bound until a device join runs.",
    buckets=[0.1, 1.0])
FRACTION = REGISTRY.gauge(
    "hvtpu_fixture_overlap_fraction",
    "Measured overlap fraction from the most recent join.")
