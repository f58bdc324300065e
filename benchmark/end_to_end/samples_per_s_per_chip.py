"""Samples completed in the window (first dispatch -> last completion)
over its seconds and the chips.  A sample is what the configuration's
file says it is (``sample_unit``)."""

UNIT = "samples/s/chip"


def read(obs):
    return (obs.window.steps * obs.samples_per_step_per_chip
            / obs.window.seconds)
