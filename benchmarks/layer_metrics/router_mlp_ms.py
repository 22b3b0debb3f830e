"""Device milliseconds per step under ``moe/route``'s leaves ``down``, ``carry`` and ``mlp``: the router's down-projection, the state carried from the layer before and the MLP's three matmuls with their norm and GELUs, forward, recomputed and backward; ``moe_route_ms`` less it is the softmax's choice, the bias, the gate and the counts (``choose``)."""

from benchmarks import scopes_cca


def read(run):
    return scopes_cca.per_step_ms(run, *(f"route/{leaf}" for leaf in scopes_cca.ROUTER))
