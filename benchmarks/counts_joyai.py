"""The work a step of the latent-attention decoder needs: what
``seq_step_mfu``, ``moe_experts_mxu_share``, ``mla_attention_mxu_share`` and
``mla_attention_hbm_share`` are shares of in its lifelong-histories cell.

Like ``counts_keye.py``: counted from what a batch really holds and the step
really chose (real tokens, causal pairs, the assignments to the experts held,
the positions with a target), never from what a kernel walks, and
recomputation is not counted. The attention's need is counted **from the
equations**, a causal pair at a time over 192 score dimensions and 128 value
dimensions a head: a program that pads the values to the scores' width, lays
the shared rotary key out a head at a time or works a pair twice reads lower,
and none can pass 100%. ``dims`` is the configuration file's own keys;
``step`` holds the step's counts: ``tokens`` and ``targets`` of the stack,
``mtp_tokens`` (the positions with a ``target_i``) and ``mtp_targets`` (those
with a ``target_{i+1}``) of the prediction module, ``causal_pairs`` and
``mtp_causal_pairs`` of one attention block of each, ``moe_held_assignments``
the step's sum over its routers.
"""

from __future__ import annotations


def attention_blocks(step: dict, dims: dict) -> tuple[float, float]:
    """``(positions, causal pairs)`` summed over the step's attention blocks:
    every layer of the stack and the prediction module's."""
    layers, module = dims["num_hidden_layers"], dims["num_nextn_predict_layers"]
    return (layers * step["tokens"] + module * step["mtp_tokens"],
            layers * step["causal_pairs"] + module * step["mtp_causal_pairs"])


def latent_attention_flops(step: dict, dims: dict) -> float:
    """Forward-and-backward flops of the attention itself over the step's
    blocks: a causal pair of a head is a score over ``qk_head_dim`` and a
    weighted sum over ``v_head_dim`` (``2 (192 + 128)``); the backward pass is
    twice the forward."""
    pair = 2.0 * dims["num_attention_heads"] * (dims["qk_head_dim"] + dims["v_head_dim"])
    return 3.0 * attention_blocks(step, dims)[1] * pair


def latent_attention_bytes(step: dict, dims: dict, itemsize: int = 2) -> float:
    """Least HBM bytes of the attention over the step's blocks, forward and
    backward: ``q``, ``k_nope``, ``v`` and the output once a position a head
    and the rotary key **once a position**, at the storage width, and the
    cotangent of each once."""
    heads = dims["num_attention_heads"]
    a_head = (dims["qk_head_dim"] + dims["qk_nope_head_dim"] + 2 * dims["v_head_dim"])
    position = (heads * a_head + dims["qk_rope_head_dim"]) * itemsize
    return 2.0 * attention_blocks(step, dims)[0] * position


def projection_flops_a_token(dims: dict) -> float:
    """Forward flops of an attention block's five projections on one token."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    return 2.0 * (d * dims["q_lora_rank"] + dims["q_lora_rank"] * h * dims["qk_head_dim"]
                  + d * (dims["kv_lora_rank"] + dims["qk_rope_head_dim"])
                  + dims["kv_lora_rank"] * h * (dims["qk_nope_head_dim"] + dims["v_head_dim"])
                  + h * dims["v_head_dim"] * d)


def step_model_flops(step: dict, dims: dict, vocab: int) -> float:
    """Forward-and-backward flops of one optimizer step, a multiply-add counted
    as two.

    Forward: an attention block on a position is its five projections
    (``projection_flops_a_token``) and ``2 H (192 + 128)`` a causal pair; a
    dense layer's MLP ``6 D F`` a token; an expert layer's router ``2 D E`` and
    shared expert ``6 D Fs`` a token and ``6 D Fe`` an assignment to a held
    expert; the module's merge ``4 D D`` a position; the head ``2 D V`` on a
    position with a target, the stack's and the module's. The backward pass is
    twice the forward. Norms, rotary positions, softmax, sigmoid, top-k, the
    bias's move and the losses are not matrix work."""
    d = dims["hidden_size"]
    dense = dims["first_k_dense_replace"]
    expert_layers = dims["num_hidden_layers"] - dense
    wide = dims["moe_intermediate_size"]
    positions, _ = attention_blocks(step, dims)
    routed_token = 2.0 * d * dims["n_routed_experts"] + 6.0 * d * dims["n_shared_experts"] * wide
    module = dims["num_nextn_predict_layers"]
    forward = (positions * projection_flops_a_token(dims)
               + step["tokens"] * dense * 6.0 * d * dims["intermediate_size"]
               + (step["tokens"] * expert_layers + step["mtp_tokens"] * module) * routed_token
               + step["moe_held_assignments"] * 6.0 * d * wide
               + step["mtp_tokens"] * module * 4.0 * d * d
               + (step["targets"] + step["mtp_targets"] * module) * 2.0 * d * vocab)
    return 3.0 * forward + latent_attention_flops(step, dims)
