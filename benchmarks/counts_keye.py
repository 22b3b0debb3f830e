"""The work a step of the sparse decoder needs: what ``seq_step_mfu``,
``sparse_attention_mxu_share``, ``sparse_attention_hbm_share`` and
``moe_experts_mxu_share`` are shares of in the lifelong-histories cell.

Like ``counts_seq.py``: counted from what a batch really holds and the step
really chose (real tokens, the pairs the indexer selected, the assignments to
the experts held, the positions with a target), never from what a kernel
walks, and recomputation is not counted. So a masked dense attention kernel
reads low, a kernel that skips what was not selected can only approach 100%,
and no share can pass it. ``dims`` is the configuration file's own keys.
"""

from __future__ import annotations


def step_model_flops(tokens: float, targets: float, selected_pairs: float,
                     causal_pairs: float, held_assignments: float, dims: dict,
                     vocab: int) -> float:
    """Forward-and-backward flops of one optimizer step, a multiply-add counted
    as two. ``selected_pairs``, ``causal_pairs`` and ``held_assignments`` are
    the step's sums over its layers; ``tokens`` and ``targets`` the batch's.

    Forward: a layer on a real token is the four attention projections
    (``2 D (2 H hd + 2 KV hd)``) and the router (``2 D E``); attention on a
    selected pair is scores and the weighted sum (``4 H hd``); an assignment to
    a held expert is its three matrices (``6 D F``); the head on a position
    with a target is ``2 D V``. The backward pass is twice the forward. The
    indexer runs forward only (it is not trained): its projections on a real
    token (``2 D (HI dI + dI + HI)``) and a score for every causal pair
    (``2 HI dI``), which a hard top-k has to see. Norms, rotary positions,
    softmax, the top-k and the losses are not matrix work.
    """
    d, hd = dims["hidden_size"], dims["head_dim"]
    h, kv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hi, di = dims["sa_config"]["indexer_num_heads"], dims["sa_config"]["indexer_head_dim"]
    layers = dims["num_hidden_layers"]
    trained = (layers * tokens * 2 * d * (2 * h * hd + 2 * kv * hd + dims["num_experts"])
               + selected_pairs * 4 * h * hd
               + held_assignments * 6 * d * dims["moe_intermediate_size"]
               + targets * 2 * d * vocab)
    indexer = layers * tokens * 2 * d * (hi * di + di + hi) + causal_pairs * 2 * hi * di
    return 3.0 * trained + indexer


def attention_call_flops(selected_pairs_a_layer: float, dims: dict) -> dict:
    """Flops one call of each attention program needs for a layer's selected
    pairs: a dot on a pair is ``2 H hd``; the forward has two (scores, weighted
    sum), ``dq`` three (scores, dP, dQ), ``dkv`` four (scores, dV, dP, dK), as
    ``counts_seq.flash_call_flops`` counts them on tiles."""
    dot = 2.0 * selected_pairs_a_layer * dims["num_attention_heads"] * dims["head_dim"]
    return {"forward": 2 * dot, "dq": 3 * dot, "dkv": 4 * dot, "backward": 7 * dot}


def attention_call_bytes(tokens: float, selected_pairs_a_layer: float, dims: dict,
                         itemsize: int = 2) -> dict:
    """Least HBM bytes of one call of each attention program for a layer: q
    and the output (or dO, dq) once a token at ``H hd`` values, K and V (or dk,
    dv) once at ``KV hd``, float32 logsumexp and delta a head, and the
    selection as a 2-byte position a selected pair."""
    hd = dims["head_dim"]
    wide = tokens * dims["num_attention_heads"] * hd * itemsize
    narrow = tokens * dims["num_key_value_heads"] * hd * itemsize
    row = tokens * dims["num_attention_heads"] * 4
    chosen = 2.0 * selected_pairs_a_layer
    forward = 2 * wide + 2 * narrow + row + chosen
    dq = 3 * wide + 2 * narrow + 2 * row + chosen          # q, dO in; dq out
    dkv = 2 * wide + 4 * narrow + 2 * row + chosen         # q, dO, k, v in; dk, dv out
    return {"forward": forward, "dq": dq, "dkv": dkv, "backward": dq + dkv}


def experts_flops(held_assignments: float, dims: dict) -> float:
    """Forward-and-backward flops of a step's held experts: three matrices an
    assignment forward, twice that backward; recomputation not counted."""
    return 3.0 * held_assignments * 6 * dims["hidden_size"] * dims["moe_intermediate_size"]
