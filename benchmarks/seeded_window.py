"""The window-and-full decoder's parameters from ``--seed``, handed to the
program and to the plain reference alike (the histories are
``seeded_lifelong.py``'s).

Pure NumPy, imports nothing of the program. ``dims`` is the configuration
file's own keys (``hidden_size``, ``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``, ``num_experts``, ...), ``held`` the experts
this share holds, ``vocab`` its rows of the vocabulary.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded

PARAM_STREAM = 14  # streams 0 to 13 are the other cells' draws
FULL, WINDOW = "full_attention", "sliding_attention"


def groups_of(dims: dict) -> tuple[int, int, int]:
    """``(periods, window layers a period, window layers after the last)`` of
    the layers after layer 0, which is full and dense: a period is the run of
    window layers up to the next full layer, and that layer."""
    kinds = list(dims["layer_types"][:dims["num_hidden_layers"]])
    mlps = list(dims["mlp_layer_types"][:dims["num_hidden_layers"]])
    rest = kinds[1:]
    inside = rest.index(FULL) if FULL in rest else len(rest)
    periods = 0
    while rest[periods * (inside + 1):(periods + 1) * (inside + 1)] == [WINDOW] * inside + [FULL]:
        periods += 1
    tail = rest[periods * (inside + 1):]
    if (kinds[0] != FULL or mlps[0] != "dense" or "dense" in mlps[1:]
            or any(k != WINDOW for k in tail) or (periods and not inside)):
        raise ValueError(f"layer_types={kinds} mlp_layer_types={mlps}: want a full dense layer,"
                         " whole periods of window layers and a full one, then window layers")
    return periods, inside if periods else 0, len(tail)


def param_shapes(dims: dict, vocab: int, held: int) -> dict:
    """The parameter tree as shapes, grouped by shape as ISSUE 44 groups it:
    ``first``, ``periods/window`` ``[P, W, ...]``, ``periods/full`` ``[P, ...]``,
    ``tail`` ``[W', ...]``; a group without layers is left out."""
    d, hd, kv = dims["hidden_size"], dims["head_dim"], dims["num_key_value_heads"]
    wide, experts = dims["moe_intermediate_size"], dims["num_experts"]
    shared, ffn = dims["shared_expert_intermediate_size"], dims["intermediate_size"]
    by_kind = dict(zip(dims["layer_types"], dims["num_attention_heads_per_layer"]))
    periods, inside, tail = groups_of(dims)

    def attention(lead, heads):
        return {"n1": lead + (d,), "wq": lead + (d, heads * hd), "wk": lead + (d, kv * hd),
                "wv": lead + (d, kv * hd), "wg": lead + (d, heads),
                "wo": lead + (heads * hd, d), "n2": lead + (d,)}

    def expert_layer(lead, heads):
        return {**attention(lead, heads), "router": lead + (d, experts),
                "w_gate": lead + (held, d, wide), "w_up": lead + (held, d, wide),
                "w_down": lead + (held, wide, d), "s_gate": lead + (d, shared),
                "s_up": lead + (d, shared), "s_down": lead + (shared, d)}

    shapes = {
        "embed": (vocab, d),
        "first": {**attention((), by_kind[FULL]), "w_gate": (d, ffn), "w_up": (d, ffn),
                  "w_down": (ffn, d)},
        "final_norm": (d,),
        "head": (vocab, d),
    }
    if periods:
        shapes["periods"] = {"window": expert_layer((periods, inside), by_kind[WINDOW]),
                             "full": expert_layer((periods,), by_kind[FULL])}
    if tail:
        shapes["tail"] = expert_layer((tail,), by_kind[WINDOW])
    return shapes


NORMS = ("n1", "n2", "final_norm")
#: the projections that write into the residual stream
RESIDUAL_WRITERS = ("wo", "w_down", "s_down")


def make_params(shapes: dict, seed: int, residual_layers: int) -> dict:
    """float32 parameters as the configuration's ``assumed`` states them:
    matrices N(0, 0.02), the embedding N(0, 1), the projections that write into
    the residual stream scaled by ``1 / sqrt(residual_layers)`` (as
    ``seeded_lifelong.make_params`` and for its reason), norm weights
    1 + N(0, 0.1) (so that a norm left out or applied twice shows)."""
    rng = seeded.rng_for(seed, PARAM_STREAM)
    writers = np.float32(0.02 / np.sqrt(residual_layers))

    def draw(name, shape):
        if isinstance(shape, dict):
            return {k: draw(k, v) for k, v in shape.items()}
        noise = rng.standard_normal(shape, dtype=np.float32)
        if name in NORMS:
            return np.float32(1.0) + np.float32(0.1) * noise
        if name == "embed":
            return noise
        return (writers if name in RESIDUAL_WRITERS else np.float32(0.02)) * noise

    return draw("", shapes)
