"""The compressed-convolution decoder's parameters from ``--seed``, handed to
the program and to the plain reference alike (the histories are
``seeded_lifelong.py``'s).

Pure NumPy, imports nothing of the program. ``dims`` is the configuration
file's own keys (``hidden_size``, ``num_attention_heads``, ``cca_time0``,
``router_hidden_size``, ``num_experts``, ...), ``held`` the experts this share
holds, ``vocab`` its rows of the vocabulary. The table is the head: there is
no ``head`` leaf.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded

PARAM_STREAM, BALANCE_STREAM = 15, 16  # streams 0 to 14 are the other cells' draws
#: the Gaussian states a router's last matrix is balanced on
BALANCE_SAMPLES = 8192

#: the spread of a router's bias as drawn. A bias of zero, or of one step's
#: 0.001, changes no choice, and a control that chooses by the softmax alone
#: could not fail; a skip that no token takes could not fail its control
#: either. At the cell's widths the softmax's largest entry lies near 0.17 with
#: its runner-up some 0.03 below, and a bias of N(0, 0.015) decides about a
#: sixth of the choices (the driver prints the share it read,
#: ``bias_decided_share``; PERF.md section 2).
BIAS_STD = 0.015

#: the half-layers' merges, (x + b_r) a_r + (y + b_y) a_y, twice a layer
SCALES = ("a_r1", "a_y1", "a_r2", "a_y2")
SHIFTS = ("b_r1", "b_y1", "b_r2", "b_y2")
NORMS = ("n1", "n2", "n_r")
#: the projections that write into the residual stream
RESIDUAL_WRITERS = ("wo", "w_down")


def param_shapes(dims: dict, vocab: int, held: int) -> dict:
    """The parameter tree as shapes; the layers' arrays are stacked
    ``[num_hidden_layers, ...]``. ``conv0_w`` ``[Lq + Lk, cca_time0]``: tap
    ``i`` of channel ``c`` reads position ``t - (taps - 1) + i``; ``conv1_w``
    ``[H + KV, cca_time1, d, d]``: tap ``i`` of head block ``g`` is the matrix
    its ``d`` channels go through; ``w_3`` and ``router_bias`` have a column
    more than there are experts, the skip's."""
    d, hd, r = dims["hidden_size"], dims["head_dim"], dims["router_hidden_size"]
    h, kv = dims["num_attention_heads"], dims["num_key_value_heads"]
    wide, choices = dims["moe_intermediate_size"], dims["num_experts"] + 1
    n = (dims["num_hidden_layers"],)
    lq, lk = h * hd, kv * hd
    return {
        "embed": (vocab, d),
        "layers": {
            "n1": n + (d,), "wq": n + (d, lq), "wk": n + (d, lk),
            "wv1": n + (d, lk // 2), "wv2": n + (d, lk // 2),
            "conv0_w": n + (lq + lk, dims["cca_time0"]), "conv0_b": n + (lq + lk,),
            "conv1_w": n + (h + kv, dims["cca_time1"], hd, hd), "conv1_b": n + (h + kv, hd),
            "tau": n + (kv,), "wo": n + (lq, d),
            "n2": n + (d,), "w_d": n + (d, r), "b_d": n + (r,), "gamma": n + (r,),
            "n_r": n + (r,), "w_1": n + (r, r), "c_1": n + (r,), "w_2": n + (r, r),
            "c_2": n + (r,), "w_3": n + (r, choices), "router_bias": n + (choices,),
            "w_gate": n + (held, d, wide), "w_up": n + (held, d, wide),
            "w_down": n + (held, wide, d),
            **{name: n + (d,) for name in SCALES + SHIFTS},
        },
        "final_norm": (d,),
    }


def make_params(shapes: dict, seed: int, residual_layers: int,
                bias_std: float = BIAS_STD) -> dict:
    """float32 parameters as the configuration's ``assumed`` states them.

    By the keye configuration's rule: matrices and biases N(0, 0.02), the
    embedding N(0, 1), the projections that write into the residual stream
    scaled by ``1 / sqrt(residual_layers)``, norm weights 1 + N(0, 0.1). This
    block's own: the temperature ``tau`` and the merges' scales 1 + N(0, 0.1),
    the merges' shifts N(0, 0.02), ``gamma`` N(0.5, 0.1), a router's bias
    N(0, ``bias_std``). Three draws keep a scale so that what they feed can
    fail a control: a convolution's taps keep their input's (a depthwise tap
    N(0, 1 / taps), a head block's matrix N(0, 1 / (taps d))), so that the
    mixed path weighs in ``q1`` and ``k1`` what the mean beside it weighs;
    the router's second and third matrices keep theirs and spread it
    (``w_2`` N(0, 1 / R), ``w_3`` N(0, 64 / R)), so that a token's softmax is
    not flat and the gate it multiplies an expert by is the token's own, and
    ``w_3`` is then balanced (``balanced``: no choice has a head start); a
    router's bias is drawn for the first half of the experts and repeated for
    the second, the skip's 0: what the bias moves it moves in both chips'
    shares alike, and the skip keeps its even share; the
    final norm's weight is ``(1 + N(0, 0.1)) / sqrt(D)``, because the table it
    is multiplied by is the embedding too, N(0, 1), and logits of unit scale
    are what a trained tied model keeps."""
    rng = seeded.rng_for(seed, PARAM_STREAM)
    writers = np.float32(0.02 / np.sqrt(residual_layers))
    balance = seeded.rng_for(seed, BALANCE_STREAM)

    def draw(name, shape):
        if isinstance(shape, dict):
            return {k: draw(k, v) for k, v in shape.items()}
        noise = rng.standard_normal(shape, dtype=np.float32)
        one = np.float32(1.0)
        if name in NORMS + SCALES + ("tau",):
            return one + np.float32(0.1) * noise
        if name == "final_norm":
            return (one + np.float32(0.1) * noise) * np.float32(shape[-1] ** -0.5)
        if name == "gamma":
            return np.float32(0.5) + np.float32(0.1) * noise
        if name == "router_bias":
            return np.float32(bias_std) * noise
        if name == "embed":
            return noise
        if name == "conv0_w":
            return np.float32(shape[-1] ** -0.5) * noise
        if name == "conv1_w":
            return np.float32((shape[-3] * shape[-2]) ** -0.5) * noise
        if name == "w_2":
            return np.float32(shape[-2] ** -0.5) * noise
        if name == "w_3":
            return np.float32(8.0 * shape[-2] ** -0.5) * noise
        return (writers if name in RESIDUAL_WRITERS else np.float32(0.02)) * noise

    params = draw("", shapes)
    layers = params["layers"]
    for n in range(layers["w_3"].shape[0]):
        layers["w_3"][n] = balanced({k: layers[k][n] for k in ROUTER_MLP}, balance)
    experts = layers["router_bias"].shape[-1] - 1
    if experts % 2 == 0:
        layers["router_bias"][:, experts // 2:experts] = layers["router_bias"][:, :experts // 2]
        layers["router_bias"][:, experts] = 0.0
    return params


ROUTER_MLP = ("n_r", "w_1", "c_1", "w_2", "c_2", "w_3")


def _gelu(x):
    """The exact GELU, its erf by Abramowitz and Stegun 7.1.26 (1.5e-7)."""
    z = np.abs(x) * np.float32(2 ** -0.5)
    t = 1.0 / (1.0 + 0.3275911 * z)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (1.421413741 + t * (
        -1.453152027 + t * 1.061405429))))
    erf = np.sign(x) * (1.0 - poly * np.exp(-z * z))
    return (0.5 * x * (1.0 + erf)).astype(np.float32)


def balanced(router: dict, rng) -> np.ndarray:
    """A router's last matrix ``w_3`` [R, choices] with every choice's logit
    given the same mean (zero) and the same spread over Gaussian states: the
    mean hidden activation is taken out of every column and the columns are
    scaled to one standard deviation, both measured on ``BALANCE_SAMPLES``
    states N(0, 1) pushed through the router's own norm and MLP.

    Why: a GELU's output has a mean, so ``W_3' mean`` gives every choice a head
    start that no token decides, and a random draw's loads read 2.6 to 3.5
    times the even load with the held experts' share anywhere from 0.44 to
    0.53 by the seed (my chip runs, PR 48). The step's time follows the rows
    the held experts take (+0.07% a +1%), and the driver's runs differ in
    seed: balanced, the share is the same on every seed to a hundredth, which
    is what a trained router's bias brings about."""
    r = rng.standard_normal((BALANCE_SAMPLES, router["w_1"].shape[0]), dtype=np.float32)
    h = r / np.sqrt((r * r).mean(axis=-1, keepdims=True) + 1e-5) * router["n_r"]
    for w, c in (("w_1", "c_1"), ("w_2", "c_2")):
        h = _gelu(h @ router[w] + router[c])
    mean = h.mean(axis=0)
    w_3 = router["w_3"] - np.outer(mean, mean @ router["w_3"]) / (mean @ mean)
    spread = (h @ w_3).std(axis=0)
    return (w_3 * (spread.mean() / spread)).astype(np.float32)
