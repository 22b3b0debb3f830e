"""The work a step of the looped decoder needs, and the work its one kernel
does: what ``seq_step_mfu`` and ``seq_attention_mxu_share`` are shares of.

Like ``counts.py``: the model's flops are counted from the tokens and targets
a batch really holds, never from padded slots, and recomputation is not
counted, so a step that pads or recomputes less cannot read over 100% and one
that does more is not flattered. The kernel's flops are what its three Pallas
programs compute for a call of the given shape, masked blocks and all (they
walk every key block under the causal mask), so its share says how much of
the MXU's peak the kernel's own arithmetic reaches. The peak is
``counts.DEVICE_PEAKS``'s bfloat16 figure.
"""

from __future__ import annotations

import numpy as np

from benchmarks import counts


def step_model_flops(seq: np.ndarray, target: np.ndarray, hidden: int, attn: int,
                     ffn: int, vocab: int, layers: int, passes: int) -> float:
    """Forward-and-backward flops of one optimizer step on the batch ``seq``
    [B, T] (0 = padding) with ``target`` [B, T] (0 = none). ``attn`` is heads x
    head width.

    Forward, a multiply-add counted as two:
    - a layer application on a real token: the four attention projections and
      the three SwiGLU matrices, ``2 (4 hidden attn + 3 hidden ffn)``;
    - attention itself on a pair of real tokens (query at or after key, one
      row): scores and the weighted sum, ``4 attn``;
    - an exit's head on a position with a target: ``2 hidden vocab``;
    every layer runs ``passes`` times and every pass has an exit. The backward
    pass is twice the forward. The embedding lookup, norms, rotary positions,
    softmax, gate and loss are left out: they are not matrix work.
    """
    lengths = (seq > 0).sum(axis=1).astype(np.float64)
    tokens = lengths.sum()
    pairs = (lengths * (lengths + 1) / 2).sum()
    targets = float((target > 0).sum())
    per_application = tokens * 2 * (4 * hidden * attn + 3 * hidden * ffn) + pairs * 4 * attn
    forward = passes * (layers * per_application + targets * 2 * hidden * vocab)
    return 3.0 * forward


def flash_call_flops(rows: int, length: int, heads: int, head_dim: int,
                     block: int = 128) -> dict:
    """Flops of one call of each of ``ops/flash_attention.py``'s programs on
    q, k, v ``[rows, length, heads, head_dim]``, as the kernels compute them:
    every ``block x block`` tile of the padded ``length x length`` square,
    above the diagonal too. Each dot of a tile is ``2 block block head_dim``:
    the forward has two (scores, weighted sum), ``dq`` three (scores, dP, dQ),
    ``dkv`` four (scores, dV, dP, dK). ``backward`` is ``dq`` + ``dkv``, the
    pair a backward pass always runs."""
    padded = -(-length // block) * block
    tile_dot = 2.0 * padded * padded * head_dim * rows * heads
    return {"forward": 2 * tile_dot, "dq": 3 * tile_dot, "dkv": 4 * tile_dot,
            "backward": 7 * tile_dot}


def mxu_share_pct(flops: float, seconds: float, device_kind: str) -> float:
    """Share of the chip's bfloat16 matrix peak: least time at the peak over
    the time taken."""
    peak = counts.device_peaks(device_kind)["bf16_flops_per_s"]
    return 100.0 * (flops / peak) / seconds
