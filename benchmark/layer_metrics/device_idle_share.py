"""One minus the time an op ran on the chip over the length of the
traced step periods kept (``benchmark/xplane.py`` says which), both
read off the device plane of the profiler's trace and averaged over
the chips: what ``device.busy_s`` and ``device.window_s`` of the result
line give.  Time a chip spends waiting inside a collective counts as
busy; that has its own metric.  The profiler slows the input down, so
in a cell the input paces this overstates what the untraced job has."""

LAYER, UNIT, MOVES = "device", "%", "samples_per_s_per_chip"


def read(obs):
    return 100.0 * obs.trace.idle_share if obs.trace else None
