"""The latent-attention decoder's parameters from ``--seed``, handed to the
program and to the plain reference alike (the histories are
``seeded_lifelong.py``'s).

Pure NumPy, imports nothing of the program. ``dims`` is the configuration
file's own keys (``hidden_size``, ``q_lora_rank``, ``n_routed_experts``, ...),
``held`` the experts this share holds, ``vocab`` its rows of the vocabulary.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded

PARAM_STREAM = 13  # streams 0 to 12 are the other cells' draws

#: the spread of a router's bias as drawn. A bias of zero, or of one step's
#: 0.001, changes no selection, and a control that selects by the scores alone
#: could not fail. At the cell's widths a router's logits are N(0, 0.9)
#: (0.02 sqrt(2048) on a normed token), a token's eighth largest of 256 sigmoid
#: scores lies near 0.84 with its neighbours some 0.01 apart, and a bias of
#: N(0, 0.02) decides about a sixth of the choices (the driver prints the
#: share it read, ``bias_decided_share``: 0.16 on the chip; PERF.md section 2).
BIAS_STD = 0.02


def param_shapes(dims: dict, vocab: int, held: int) -> dict:
    """The parameter tree as shapes: ``dense`` ``[first_k_dense_replace, ...]``,
    ``layers`` ``[the expert layers, ...]``, ``mtp/layer`` one expert layer."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    nope, rope, value = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], dims["v_head_dim"]
    q_rank, kv_rank = dims["q_lora_rank"], dims["kv_lora_rank"]
    wide, experts = dims["moe_intermediate_size"], dims["n_routed_experts"]
    shared = dims["n_shared_experts"] * wide
    dense = dims["first_k_dense_replace"]

    def attention(lead):
        return {"n1": lead + (d,), "w_qa": lead + (d, q_rank), "q_norm": lead + (q_rank,),
                "w_qb": lead + (q_rank, h * (nope + rope)),
                "w_kva": lead + (d, kv_rank + rope), "kv_norm": lead + (kv_rank,),
                "w_kvb": lead + (kv_rank, h * (nope + value)),
                "wo": lead + (h * value, d), "n2": lead + (d,)}

    def expert_layer(lead):
        return {**attention(lead), "router": lead + (d, experts),
                "router_bias": lead + (experts,),
                "w_gate": lead + (held, d, wide), "w_up": lead + (held, d, wide),
                "w_down": lead + (held, wide, d), "s_gate": lead + (d, shared),
                "s_up": lead + (d, shared), "s_down": lead + (shared, d)}

    k, ffn = (dense,), dims["intermediate_size"]
    shapes = {
        "embed": (vocab, d),
        "dense": {**attention(k), "w_gate": k + (d, ffn), "w_up": k + (d, ffn),
                  "w_down": k + (ffn, d)},
        "layers": expert_layer((dims["num_hidden_layers"] - dense,)),
        "final_norm": (d,),
        "head": (vocab, d),
    }
    if dims["num_nextn_predict_layers"]:
        shapes["mtp"] = {"embed_norm": (d,), "hidden_norm": (d,), "merge": (2 * d, d),
                         "layer": expert_layer(()), "final_norm": (d,)}
    return shapes


NORMS = ("n1", "n2", "q_norm", "kv_norm", "final_norm", "embed_norm", "hidden_norm")
#: the projections that write into the residual stream
RESIDUAL_WRITERS = ("wo", "w_down", "s_down")


def make_params(shapes: dict, seed: int, residual_layers: int,
                bias_std: float = BIAS_STD) -> dict:
    """float32 parameters as the configuration's ``assumed`` states them:
    matrices N(0, 0.02), the embedding N(0, 1), the projections that write into
    the residual stream scaled by ``1 / sqrt(residual_layers)`` (as
    ``seeded_lifelong.make_params`` and for its reason), norm weights
    1 + N(0, 0.1) (so that a norm left out or applied twice shows), a router's
    bias N(0, ``bias_std``)."""
    rng = seeded.rng_for(seed, PARAM_STREAM)
    writers = np.float32(0.02 / np.sqrt(residual_layers))

    def draw(name, shape):
        if isinstance(shape, dict):
            return {k: draw(k, v) for k, v in shape.items()}
        noise = rng.standard_normal(shape, dtype=np.float32)
        if name in NORMS:
            return np.float32(1.0) + np.float32(0.1) * noise
        if name == "router_bias":
            return np.float32(bias_std) * noise
        if name == "embed":
            return noise
        return (writers if name in RESIDUAL_WRITERS else np.float32(0.02)) * noise

    return draw("", shapes)
