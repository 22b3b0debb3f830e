"""What the lifelong-histories cell draws from ``--seed``: users whose
histories are all at least ``min_events`` long, and the sparse decoder's
parameters, handed to the program and to the plain reference alike.

Pure NumPy, imports nothing of the program. A history's length is
``min_events`` plus an exponential tail whose mean brings the users' mean to
``mean_events``; its items are drawn from a shifted Zipf law over the ids
(``popularity``), the ids relabelled by the seed. Every user fills a row of
``min_events`` or fewer slots, so a step's tokens do not depend on the seed;
only as many users are made as a run takes several times over.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded, seeded_histories

#: streams of a seed (0 to 8 are taken by the other cells' draws)
LENGTH_STREAM, ITEM_STREAM, QUERY_STREAM = 9, 10, 11


def make_histories(data: dict, n_users: int, n_items: int, seed: int) -> list[np.ndarray]:
    """One array of 0-based item indexes a user, oldest first."""
    law = data["popularity"]
    weight = (np.arange(n_items) + law["shift"]) ** -float(law["exponent"])
    cumulative = np.cumsum(weight / weight.sum())
    lengths = data["min_events"] + seeded.rng_for(seed, LENGTH_STREAM).exponential(
        data["mean_events"] - data["min_events"], n_users).astype(np.int64)
    rng = seeded.rng_for(seed, ITEM_STREAM)
    names = rng.permutation(n_items)             # which id is how popular
    drawn = np.searchsorted(cumulative, rng.random(int(lengths.sum())))
    drawn = names[np.minimum(drawn, n_items - 1)].astype(np.int64)
    return np.split(drawn, np.cumsum(lengths)[:-1])


def param_shapes(vocab: int, hidden: int, heads: int, kv_heads: int, head_dim: int,
                 expert_dim: int, experts: int, held: int, layers: int,
                 index_heads: int, index_dim: int) -> dict:
    """The sparse decoder's parameter tree as shapes; the layers' arrays are
    stacked ``[L, ...]``; ``w_gate``, ``w_up``, ``w_down`` hold the ``held``
    experts of this share, the router all ``experts`` columns."""
    d, n = hidden, layers
    return {
        "embed": (vocab, d),
        "layers": {
            "n1": (n, d), "wq": (n, d, heads * head_dim), "wk": (n, d, kv_heads * head_dim),
            "wv": (n, d, kv_heads * head_dim), "wo": (n, heads * head_dim, d),
            "n2": (n, d), "router": (n, d, experts),
            "w_gate": (n, held, d, expert_dim), "w_up": (n, held, d, expert_dim),
            "w_down": (n, held, expert_dim, d),
        },
        "indexer": {"wq": (n, d, index_heads * index_dim), "wk": (n, d, index_dim),
                    "ww": (n, d, index_heads)},
        "final_norm": (d,),
        "head": (vocab, d),
    }


#: the projections that write into the residual stream
RESIDUAL_WRITERS = ("wo", "w_down")


def make_params(shapes: dict, seed: int, residual_layers: int) -> dict:
    """float32 parameters as ``seeded_histories.make_params`` draws them
    (matrices N(0, 0.02), norm weights 1 + N(0, 0.1)), but the embedding
    N(0, 1) (``torch.nn.Embedding``'s own default) and the projections that
    write into the residual stream (``W_o``, every expert's ``W_down``) scaled
    by ``1 / sqrt(residual_layers)`` (GPT-2's initialisation, Radford et al.
    2019, section 2.3). Together they keep a position's state its own token's
    in every layer, as a trained model's is. Drawn at 0.02 throughout, the
    near-uniform attention of random weights adds the same running mean of
    values to every position, the deeper routers see one state and send a
    layer's tokens to the same few experts, and whether a chip holds them is
    the seed's luck (PERF.md, PR 33)."""
    params = seeded_histories.make_params(shapes, seed)
    params["embed"] = params["embed"] * np.float32(1.0 / 0.02)
    scale = np.float32(1.0 / np.sqrt(residual_layers))
    for name in RESIDUAL_WRITERS:
        params["layers"][name] = params["layers"][name] * scale
    return params


def probe_queries(max_len: int, size: int, seed: int) -> np.ndarray:
    """Sampled query positions whose index scores and selection are compared:
    the last position always, the rest anywhere in the row."""
    rows = seeded.sample_rows(max_len - 1, size - 1, seed, QUERY_STREAM)
    return np.concatenate([rows, [max_len - 1]]).astype(np.int32)
