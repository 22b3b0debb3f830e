"""The hybrid decoder's parameters from ``--seed``, handed to the program and
to the plain reference alike (the histories are ``seeded_lifelong.py``'s).

Pure NumPy, imports nothing of the program. ``dims`` is the configuration
file's own keys (``hidden_size``, ``linear_num_key_heads``, ...), ``held`` the
experts this share holds, ``vocab`` its rows of the vocabulary.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded

PARAM_STREAM = 12  # streams 0 to 11 are the other cells' draws


def param_shapes(dims: dict, vocab: int, held: int) -> dict:
    """The parameter tree as shapes: ``periods/linear`` ``[P, I - 1, ...]`` and
    ``periods/full`` ``[P, ...]`` for ``P`` periods of ``I`` layers, each with
    its layers' mixer and experts."""
    d, i = dims["hidden_size"], dims["full_attention_interval"]
    p = dims["num_hidden_layers"] // i
    keys = dims["linear_num_key_heads"] * dims["linear_key_head_dim"]
    values = dims["linear_num_value_heads"] * dims["linear_value_head_dim"]
    heads, kv, hd = dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    wide, shared = dims["moe_intermediate_size"], dims["shared_expert_intermediate_size"]

    def experts(lead):
        return {"n2": lead + (d,), "router": lead + (d, dims["num_experts"]),
                "w_gate": lead + (held, d, wide), "w_up": lead + (held, d, wide),
                "w_down": lead + (held, wide, d), "s_gate": lead + (d, shared),
                "s_up": lead + (d, shared), "s_down": lead + (shared, d), "s_g": lead + (d,)}

    lin = (p, i - 1)
    return {
        "embed": (vocab, d),
        "periods": {
            "linear": {
                "n1": lin + (d,), "w_qkvz": lin + (d, 2 * keys + 2 * values),
                "w_ba": lin + (d, 2 * dims["linear_num_value_heads"]),
                "conv": lin + (2 * keys + values, dims["linear_conv_kernel_dim"]),
                "a_log": lin + (dims["linear_num_value_heads"],),
                "dt_bias": lin + (dims["linear_num_value_heads"],),
                "norm": lin + (dims["linear_value_head_dim"],), "w_out": lin + (values, d),
                **experts(lin)},
            "full": {
                "n1": (p, d), "wq": (p, d, 2 * heads * hd), "wk": (p, d, kv * hd),
                "wv": (p, d, kv * hd), "wo": (p, heads * hd, d), "q_norm": (p, hd),
                "k_norm": (p, hd), **experts((p,))},
        },
        "final_norm": (d,),
        "head": (vocab, d),
    }


#: zero-centred norm weights, drawn about 0; the gated norm's plain weight about 1
ZERO_CENTRED = ("n1", "n2", "q_norm", "k_norm", "final_norm")
#: the projections that write into the residual stream
RESIDUAL_WRITERS = ("w_out", "wo", "w_down", "s_down")


def make_params(shapes: dict, seed: int, residual_layers: int) -> dict:
    """float32 parameters as the configuration's ``assumed`` states them:
    matrices N(0, 0.02), the embedding N(0, 1), the projections that write into
    the residual stream scaled by ``1 / sqrt(residual_layers)`` (as
    ``seeded_lifelong.make_params`` and for its reason), zero-centred norm
    weights N(0, 0.1) and the gated norm's 1 + N(0, 0.1) (so that a norm left
    out or applied twice shows), the conv U(-1/2, 1/2), ``A_log = log U(0, 16)``,
    ``dt_bias = 1``."""
    rng = seeded.rng_for(seed, PARAM_STREAM)
    writers = np.float32(0.02 / np.sqrt(residual_layers))

    def draw(name, shape):
        if isinstance(shape, dict):
            return {k: draw(k, v) for k, v in shape.items()}
        if name == "dt_bias":
            return np.ones(shape, np.float32)
        if name == "a_log":
            return np.log(rng.uniform(1e-3, 16.0, shape)).astype(np.float32)
        if name == "conv":
            return rng.uniform(-0.5, 0.5, shape).astype(np.float32)
        noise = rng.standard_normal(shape, dtype=np.float32)
        if name in ZERO_CENTRED:
            return np.float32(0.1) * noise
        if name == "norm":
            return np.float32(1.0) + np.float32(0.1) * noise
        if name == "embed":
            return noise
        return (writers if name in RESIDUAL_WRITERS else np.float32(0.02)) * noise

    return draw("", shapes)
