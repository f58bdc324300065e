"""Share of the window the loop's thread spent waiting, in
``next(batches)`` or in the fence, on the benchmark's clock around the
two calls.  With two steps in flight and a loader that runs near the
step's pace the host blocks in one or the other by a coin's toss from
run to run (0.4 to 97 % in ``next`` for the same throughput, PR 22), so
only their sum repeats.  What is left of the window is the host's own
work per step; as this nears zero the host paces the job."""

LAYER, UNIT, MOVES = "dispatch", "%", "samples_per_s_per_chip"


def read(obs):
    return 100.0 * (obs.window.span_share("data_next")
                    + obs.window.span_share("fence_wait"))
