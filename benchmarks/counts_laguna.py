"""The work a step of the window-and-full decoder needs: what ``seq_step_mfu``,
``moe_experts_mxu_share``, ``window_attention_mxu_share`` and
``window_attention_hbm_share`` are shares of in its lifelong-histories cell.

Like ``counts_keye.py``: counted from what a batch really holds and the step
really chose (real tokens, the pairs of the band and of the triangle between
real positions, the assignments to the experts held, the positions with a
target), never from what a kernel walks, and recomputation is not counted. A
window layer's attention is counted **from the equations**, a pair of the band
at a time (``t - window < s <= t``) over 128 score dimensions and 128 value
dimensions a head: a program that walks the triangle, or tiles the band half
fills, reads lower, and none can pass 100%. ``dims`` is the configuration
file's own keys; ``step`` holds the step's counts: ``tokens`` and ``targets``,
``causal_pairs`` and ``window_pairs`` of one attention layer of each kind,
``moe_held_assignments`` the step's sum over its routers.
"""

from __future__ import annotations

FULL, WINDOW = "full_attention", "sliding_attention"


def layers_by_kind(dims: dict) -> dict:
    """``{kind: (layers, heads)}`` of the layers the file holds."""
    kinds = dims["layer_types"][:dims["num_hidden_layers"]]
    heads = dims["num_attention_heads_per_layer"][:dims["num_hidden_layers"]]
    return {kind: (kinds.count(kind), dict(zip(kinds, heads)).get(kind, 0))
            for kind in (FULL, WINDOW)}


def pairs_of(lengths, window: int) -> tuple[float, float]:
    """``(causal pairs, band pairs)`` of rows with ``lengths`` real positions,
    left-aligned: a position reads itself and what came before it, in a window
    layer the last ``window`` of them."""
    causal = band = 0.0
    for n in lengths:
        n, w = float(n), float(min(window, n))
        causal += n * (n + 1) / 2
        band += w * (w + 1) / 2 + (n - w) * w
    return causal, band


def window_attention_flops(step: dict, dims: dict) -> float:
    """Forward-and-backward flops of the window layers' attention itself: a
    pair of the band is a score over ``head_dim`` and a weighted sum over
    ``head_dim`` a head (``2 H (128 + 128)``); the backward pass is twice the
    forward."""
    layers, heads = layers_by_kind(dims)[WINDOW]
    return 3.0 * layers * step["window_pairs"] * 2.0 * heads * 2 * dims["head_dim"]


def full_attention_flops(step: dict, dims: dict) -> float:
    """The same of the full layers, a causal pair at a time."""
    layers, heads = layers_by_kind(dims)[FULL]
    return 3.0 * layers * step["causal_pairs"] * 2.0 * heads * 2 * dims["head_dim"]


def window_attention_bytes(step: dict, dims: dict, itemsize: int = 2) -> float:
    """Least HBM bytes of the window layers' attention, forward and backward:
    ``q`` and the output once a position a head, ``k`` and ``v`` once a
    position a key-value head, at the storage width, and the cotangent of each
    once."""
    layers, heads = layers_by_kind(dims)[WINDOW]
    position = (2 * heads + 2 * dims["num_key_value_heads"]) * dims["head_dim"] * itemsize
    return 2.0 * layers * step["tokens"] * position


def projection_flops_a_token(heads: int, dims: dict) -> float:
    """Forward flops of an attention layer's five projections on one token:
    ``W_q``, ``W_o`` (``H hd``), ``W_k``, ``W_v`` (``KV hd``) and the gate's
    ``W_g`` (``H``)."""
    d, hd = dims["hidden_size"], dims["head_dim"]
    return 2.0 * d * (2 * heads * hd + 2 * dims["num_key_value_heads"] * hd + heads)


def forward_parts(step: dict, dims: dict, vocab: int) -> dict:
    """Forward flops of one step by part, a multiply-add counted as two: what
    ``PERF.md`` splits the need by."""
    d, tokens = dims["hidden_size"], step["tokens"]
    by_kind = layers_by_kind(dims)
    expert_layers = dims["num_hidden_layers"] - 1
    return {
        "full_projections": tokens * by_kind[FULL][0] * projection_flops_a_token(
            by_kind[FULL][1], dims),
        "full_pairs": full_attention_flops(step, dims) / 3.0,
        "window_projections": tokens * by_kind[WINDOW][0] * projection_flops_a_token(
            by_kind[WINDOW][1], dims),
        "window_pairs": window_attention_flops(step, dims) / 3.0,
        "dense_mlp": tokens * 6.0 * d * dims["intermediate_size"],
        "router": tokens * expert_layers * 2.0 * d * dims["num_experts"],
        "shared_experts": tokens * expert_layers * 6.0 * d
        * dims["shared_expert_intermediate_size"],
        "held_experts": step["moe_held_assignments"] * 6.0 * d * dims["moe_intermediate_size"],
        "head": step["targets"] * 2.0 * d * vocab,
    }


def step_model_flops(step: dict, dims: dict, vocab: int) -> float:
    """Forward-and-backward flops of one optimizer step: three times
    ``forward_parts``'s sum (the backward pass is twice the forward). Band
    pairs for the window layers, causal pairs for the full ones; layer 0's MLP
    ``6 D F`` a token; an expert layer's router ``2 D E`` and shared expert
    ``6 D Fs`` a token and ``6 D Fe`` an assignment to a held expert; the head
    ``2 D V`` on a position with a target. Norms, rotary positions, softmax,
    sigmoid, top-k and the losses are not matrix work."""
    return 3.0 * sum(forward_parts(step, dims, vocab).values())
