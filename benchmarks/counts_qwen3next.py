"""The work a step of the hybrid decoder needs: what ``seq_step_mfu``,
``moe_experts_mxu_share``, ``linattn_delta_mxu_share`` and
``linattn_delta_hbm_share`` are shares of in its lifelong-histories cell.

Like ``counts_keye.py``: counted from what a batch really holds and the step
really chose (real tokens, causal pairs, the assignments to the experts held,
the positions with a target), never from what a kernel walks, and
recomputation is not counted. The delta rule's need is counted **from the
recurrence**, a token at a time, not from the chunked form that works it: a
chunk size or a kernel that does more arithmetic a token reads lower, and none
can pass 100%. ``dims`` is the configuration file's own keys.
"""

from __future__ import annotations


def delta_rule_flops(tokens: float, linear_layers: int, dims: dict) -> float:
    """Forward-and-backward flops of the recurrence itself over a step's
    linear layers. A token of a value head reads the state by its key
    (``u = S^T k``: ``2 dk dv``), writes the correction (``S += k d^T``:
    ``2 dk dv``) and reads the state by its query (``o = S^T q``: ``2 dk dv``);
    the decay is not matrix work. The backward pass is twice the forward."""
    per_token = 6.0 * dims["linear_key_head_dim"] * dims["linear_value_head_dim"]
    return 3.0 * tokens * linear_layers * dims["linear_num_value_heads"] * per_token


def delta_rule_bytes(tokens: float, linear_layers: int, dims: dict, itemsize: int = 2) -> float:
    """Least HBM bytes of the rule over a step's linear layers, forward and
    backward: ``q`` and ``k`` once a token a key head, ``v`` and the output a
    value head, ``g`` and ``beta`` (float32) a value head, and the cotangent of
    each once in the backward pass, which reads ``q``, ``k``, ``v``, ``g``,
    ``beta`` again; the state stays on the chip."""
    hk, hv = dims["linear_num_key_heads"], dims["linear_num_value_heads"]
    dk, dv = dims["linear_key_head_dim"], dims["linear_value_head_dim"]
    inputs = (2 * hk * dk + hv * dv) * itemsize + 2 * hv * 4
    output = hv * dv * 4
    forward = inputs + output
    backward = inputs + output + inputs                # read again, dO in, cotangents out
    return tokens * linear_layers * float(forward + backward)


def step_model_flops(tokens: float, targets: float, causal_pairs: float,
                     held_assignments: float, dims: dict, vocab: int) -> float:
    """Forward-and-backward flops of one optimizer step, a multiply-add counted
    as two. ``causal_pairs`` is the batch's (a full layer reads every one),
    ``held_assignments`` the step's sum over its layers.

    Forward, a real token: a linear layer's projections
    (``2 D (2 HK dk + 2 HV dv + 2 HV)`` in, ``2 HV dv D`` out) and its rule
    (``delta_rule_flops``); a full layer's (``2 D (2 H hd + 2 KV hd)`` in,
    ``2 H hd D`` out) and ``4 H hd`` a causal pair; every layer's router
    (``2 D E``) and shared expert (``6 D Fs``); an assignment to a held expert
    ``6 D F``; the head on a position with a target ``2 D V``. The backward pass
    is twice the forward. The conv, norms, gates, rotary positions, softmax,
    top-k and losses are not matrix work."""
    d = dims["hidden_size"]
    interval = dims["full_attention_interval"]
    full = dims["num_hidden_layers"] // interval
    linear = dims["num_hidden_layers"] - full
    hk, hv = dims["linear_num_key_heads"], dims["linear_num_value_heads"]
    dk, dv = dims["linear_key_head_dim"], dims["linear_value_head_dim"]
    h, kv, hd = dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    linear_token = 2 * d * (2 * hk * dk + 2 * hv * dv + 2 * hv) + 2 * hv * dv * d
    full_token = 2 * d * (2 * h * hd + 2 * kv * hd) + 2 * h * hd * d
    every_token = 2 * d * dims["num_experts"] + 6 * d * dims["shared_expert_intermediate_size"]
    forward = (tokens * (linear * linear_token + full * full_token
                         + dims["num_hidden_layers"] * every_token)
               + full * causal_pairs * 4 * h * hd
               + held_assignments * 6 * d * dims["moe_intermediate_size"]
               + targets * 2 * d * vocab)
    return 3.0 * forward + delta_rule_flops(tokens, linear, dims)
