"""Device time a step under the expert layer's scopes but the shared
expert's (``hvtpu:moe.route``, ``.dispatch``, ``.experts``,
``.combine``): the router, the plan, the row movement both ways and the
held experts' products, forward, recomputed and backward, summed over
the expert layers.  ``moe_ms_per_step`` is the transformer cell's, which
has no shared expert.

Where the products run as ``lax.ragged_dot`` (the ungated experts,
1,856 wide: the layer counts the path when it is traced), XLA:TPU builds
each as kernels of its own and names them by itself (``ragged-dot-none``
and ``ragged-dot-metadata``, no ``op_name`` of the program's), so no
scope reaches them: their device time is found by the instructions'
names and counted here, and in ``relu2_expert_roofline``, with
``hvtpu:moe.experts``."""

from benchmark import scopes

LAYER, UNIT, MOVES = "moe", "ms", "samples_per_s_per_chip"

SHARED = "hvtpu:moe.shared"
COMPILERS_PRODUCTS = "ragged-dot"


def compilers_products_ms(obs) -> float:
    """Device ms a step in the kernels XLA made of ``lax.ragged_dot``,
    by their instructions' names; 0 where there are none."""
    if not obs.trace:
        return 0.0
    devices = obs.trace.devices
    return sum(
        ns / (1e6 * len(device.step_ns) * len(devices))
        for device in devices for op, ns in device.op_ns.items()
        if op.split(" ", 1)[0].startswith(COMPILERS_PRODUCTS))


def experts_ms(obs):
    """``hvtpu:moe.experts`` with the compiler's own kernels; None where
    there is nothing to read."""
    ms = scopes.scoped_ms(obs, "hvtpu:moe.experts")
    return ms and ms + compilers_products_ms(obs)


def read(obs):
    by_scope = scopes.ms_per_step(obs.trace, obs.compiled_text)
    if by_scope is None:
        return None
    found = [ms for scope, ms in by_scope.items()
             if scope.startswith("hvtpu:moe.") and scope != SHARED]
    return sum(found) + compilers_products_ms(obs) if found else None
