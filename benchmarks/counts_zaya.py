"""The work a step of the compressed-convolution decoder needs: what
``seq_step_mfu``, ``moe_experts_mxu_share``, ``cca_attention_mxu_share`` and
``cca_mix_hbm_share`` are shares of in its lifelong-histories cell.

Like ``counts_keye.py``: counted from what a batch really holds and the step
really chose (real tokens, the causal pairs between real positions, the
assignments to the experts held, the positions with a target), never from what
a kernel walks, and recomputation is not counted. ``dims`` is the configuration
file's own keys; ``step`` holds the step's counts: ``tokens`` and ``targets``,
``causal_pairs`` of one attention layer, ``moe_held_assignments`` the step's
sum over its routers.

    python benchmarks/counts_zaya.py

prints ``forward_parts`` of the cell's step at an even router (8 of 17 choices
held), in TFLOP.
"""

from __future__ import annotations


def widths(dims: dict) -> tuple[int, int]:
    """``(Lq, Lk)``: the query latent's and the key-value latent's channels."""
    return (dims["num_attention_heads"] * dims["head_dim"],
            dims["num_key_value_heads"] * dims["head_dim"])


def pairs_of(lengths) -> float:
    """The causal pairs of rows with ``lengths`` real positions, left-aligned:
    a position reads itself and what came before it."""
    return float(sum(float(n) * (float(n) + 1) / 2 for n in lengths))


def attention_flops(step: dict, dims: dict) -> float:
    """Forward-and-backward flops of the attention programs themselves: a
    causal pair is seven dots over ``head_dim`` a query head, two forward (the
    score, the weighted sum) and five backward (the score again, which the
    backward program has to work, and ``dv``, ``dp``, ``dq``, ``dk``); the
    forward pass worked again for the rematerialisation is not counted."""
    layers, heads = dims["num_hidden_layers"], dims["num_attention_heads"]
    return 7.0 * layers * step["causal_pairs"] * heads * 2.0 * dims["head_dim"]


def mix_bytes(step: dict, dims: dict) -> float:
    """Least HBM bytes of the stage between the projections and the attention's
    operands, float32, over the three phases of a step: forward and recomputed
    each read the stage's inputs once (``q0``, ``k0`` and the value's two
    halves: ``Lq + 2 Lk`` floats a token) and write its outputs once (``q2``,
    ``k2``, ``v``: as many); backward reads the outputs' cotangents and the
    inputs and writes the inputs' cotangents. The parameters are a few
    hundred kilobytes a layer and are not counted."""
    lq, lk = widths(dims)
    token = 4.0 * (lq + 2 * lk)
    return dims["num_hidden_layers"] * step["tokens"] * token * (2 + 2 + 3)


def projection_flops_a_token(dims: dict) -> float:
    """Forward flops of the attention half's matmuls on one token: ``W_q``,
    ``W_o`` (``Lq``), ``W_k`` (``Lk``), ``W_v1`` and ``W_v2`` (``Lk`` together)
    and the head blocks' convolution (``taps d`` inputs a channel)."""
    d, hd = dims["hidden_size"], dims["head_dim"]
    lq, lk = widths(dims)
    return 2.0 * d * (2 * lq + 2 * lk) + 2.0 * (lq + lk) * dims["cca_time1"] * hd


def router_flops_a_token(dims: dict) -> float:
    """Forward flops of a router on one token: the down-projection, the MLP's
    two square matrices and its last over the experts and the skip."""
    r = dims["router_hidden_size"]
    return 2.0 * (dims["hidden_size"] * r + 2 * r * r + r * (dims["num_experts"] + 1))


def forward_parts(step: dict, dims: dict, vocab: int) -> dict:
    """Forward flops of one step by part, a multiply-add counted as two: what
    ``PERF.md`` splits the need by."""
    d, tokens, layers = dims["hidden_size"], step["tokens"], dims["num_hidden_layers"]
    return {
        "causal_pairs": 2.0 * layers * step["causal_pairs"] * dims["num_attention_heads"]
        * 2.0 * dims["head_dim"],
        "cca_projections_and_convolution": tokens * layers * projection_flops_a_token(dims),
        "routers": tokens * layers * router_flops_a_token(dims),
        "held_experts": step["moe_held_assignments"] * 6.0 * d * dims["moe_intermediate_size"],
        "head": step["targets"] * 2.0 * d * vocab,
    }


def step_model_flops(step: dict, dims: dict, vocab: int) -> float:
    """Forward-and-backward flops of one optimizer step: three times
    ``forward_parts``'s sum (the backward pass is twice the forward). The
    depthwise convolution, the mean, norms, rotary positions, softmax, GELU
    and the loss are not matrix work."""
    return 3.0 * sum(forward_parts(step, dims, vocab).values())


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "zaya1-8b-ep2.json")) as f:
        config = json.load(f)
    rows, length = 2, 16384
    even = {"tokens": float(rows * length), "targets": float(rows * (length - 1)),
            "causal_pairs": pairs_of([length] * rows),
            "moe_held_assignments": rows * length * config["num_hidden_layers"]
            * config["num_local_experts"] / (config["num_experts"] + 1)}
    parts = forward_parts(even, config, config["vocab_size"])
    print(json.dumps({**{k: round(v / 1e12, 3) for k, v in parts.items()},
                      "forward_tflop": round(sum(parts.values()) / 1e12, 3)}, indent=1))
