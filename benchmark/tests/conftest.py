"""Tests of the benchmark's own code, run by hand:

    python -m pytest benchmark/tests -q

They are no part of the repository's tier-1 suite (``tests/``).  Four
virtual CPU devices, set before anything touches a backend; the cells
themselves are walked in child processes (``--rehearse-on-cpu``).
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
