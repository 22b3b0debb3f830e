"""What a sequence cell draws from ``--seed``: the users' histories and the
looped decoder's parameters, handed to the program and to the plain reference
alike.

Pure NumPy, imports nothing of the program. Who saw what is
``seeded.make_ratings`` (each side's degrees along the quantile curve the
configuration gives, the structure fixed by ``structure_seed``, the seed
relabelling users and items); a history is a user's events in the order drawn.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded

#: streams of a seed (0 to 5 are taken by the ALS cells' draws)
PARAM_STREAM, ORDER_STREAM, HEAD_ROW_STREAM = 6, 7, 8


def make_histories(data: dict, n_events: int, n_users: int, n_items: int,
                   seed: int) -> list[np.ndarray]:
    """One array of 0-based item indexes a user, in user order: the lengths
    follow ``data["user_degrees"]``, the items' popularity
    ``data["item_degrees"]``."""
    users, items, _ = seeded.make_ratings(data, n_events, n_users, n_items, seed)
    order = np.argsort(users, kind="stable")  # within a user, the order drawn
    bounds = np.cumsum(np.bincount(users, minlength=n_users))[:-1]
    return np.split(items[order].astype(np.int64), bounds)


def param_shapes(vocab: int, hidden: int, attn: int, ffn: int, layers: int) -> dict:
    """The looped decoder's parameter tree as shapes; the layers' arrays are
    stacked ``[L, ...]``. ``attn`` is heads x head width."""
    d, a, f, n = hidden, attn, ffn, layers
    return {
        "embed": (vocab, d),
        "layers": {
            "n1": (n, d), "wq": (n, d, a), "wk": (n, d, a), "wv": (n, d, a),
            "wo": (n, a, d), "n2": (n, d),
            "n3": (n, d), "w_gate": (n, d, f), "w_up": (n, d, f),
            "w_down": (n, f, d), "n4": (n, d),
        },
        "final_norm": (d,),
        "head": (vocab, d),
        "gate_w": (d,),
        "gate_b": (),
    }


NORMS = ("n1", "n2", "n3", "n4", "final_norm")


def make_params(shapes: dict, seed: int, stream: int = PARAM_STREAM) -> dict:
    """float32 parameters: matrices and the gate N(0, 0.02), norm weights
    1 + N(0, 0.1) (so that a norm left out or applied twice shows), the
    gate's bias N(0, 0.02)."""
    rng = seeded.rng_for(seed, stream)

    def draw(name, shape):
        if isinstance(shape, dict):
            return {k: draw(k, v) for k, v in shape.items()}
        noise = rng.standard_normal(shape, dtype=np.float32)
        if name in NORMS:
            return np.float32(1.0) + np.float32(0.1) * noise
        return np.float32(0.02) * noise

    return draw("", shapes)


def batch_order(n_users: int, seed: int) -> np.ndarray:
    """The users in the order the steps take them: no repeats."""
    return seeded.rng_for(seed, ORDER_STREAM).permutation(n_users)


def head_rows(vocab: int, size: int, seed: int) -> np.ndarray:
    """Sampled item ids (never the padding id 0) whose head rows' gradients
    are compared."""
    return 1 + seeded.sample_rows(vocab - 1, size, seed, HEAD_ROW_STREAM)
