"""Classification kernels: multinomial Naive Bayes + logistic regression.

TPU-native replacements for the MLlib algorithms the stock classification
template invokes (``org.apache.spark.mllib.classification.{NaiveBayes,
LogisticRegressionWithLBFGS}`` -- Spark deps, SURVEY.md section 2.8):

- NB training is ONE matmul: ``onehot(labels).T @ X`` gives the class-
  conditional count matrix on the MXU; smoothing + log happens elementwise.
- LogReg trains full-batch with optax (L-BFGS when available, matching
  MLlib's optimizer; Adam fallback), all jitted.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import optax


@dataclass
class NaiveBayesModel:
    log_prior: np.ndarray       # [C]
    log_likelihood: np.ndarray  # [C, D]

    def scores(self, x: np.ndarray) -> np.ndarray:
        """Log-posterior (unnormalized) per class: [n, C]."""
        return x @ self.log_likelihood.T + self.log_prior


def train_naive_bayes(
    x: np.ndarray,
    y: np.ndarray,
    num_classes: int,
    smoothing: float = 1.0,
    mesh=None,
) -> NaiveBayesModel:
    """Multinomial NB: the count matrix is ONE matmul.

    With ``mesh``, examples shard over the ``data`` axis (zero-weight
    padding rows are masked out of the one-hot, so they contribute no
    counts) and the count matmul's cross-example reduction becomes an
    XLA-inserted psum -- MLlib NaiveBayes' per-partition aggregate+combine,
    as GSPMD sharding.
    """
    # multinomial NB is defined over counts; negative features would poison
    # the log with NaNs (MLlib's NaiveBayes rejects them the same way)
    if np.min(x) < 0:
        raise ValueError(
            "NaiveBayes requires non-negative features (multinomial counts);"
            " use logistic-regression for signed features"
        )
    from predictionio_tpu.parallel.mesh import shard_examples

    x_j, y_j, w_j, mesh = shard_examples(mesh, x, y)

    @jax.jit
    def _fit(x, y, w):
        onehot = jax.nn.one_hot(y, num_classes, dtype=x.dtype)       # [n, C]
        onehot = onehot * w[:, None]        # padding rows count nothing
        counts = onehot.T @ x                                        # [C, D] one MXU pass
        class_counts = onehot.sum(axis=0)                            # [C]
        log_prior = jnp.log(class_counts + smoothing) - jnp.log(
            w.sum() + num_classes * smoothing
        )
        smoothed = counts + smoothing
        log_likelihood = jnp.log(smoothed) - jnp.log(
            smoothed.sum(axis=1, keepdims=True)
        )
        return log_prior, log_likelihood

    log_prior, log_likelihood = _fit(x_j, y_j, w_j)
    return NaiveBayesModel(np.asarray(log_prior), np.asarray(log_likelihood))


@dataclass
class LogisticRegressionModel:
    weights: np.ndarray  # [D, C]
    bias: np.ndarray     # [C]

    def scores(self, x: np.ndarray) -> np.ndarray:
        logits = x @ self.weights + self.bias
        e = np.exp(logits - logits.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)


def train_logistic_regression(
    x: np.ndarray,
    y: np.ndarray,
    num_classes: int,
    reg: float = 1e-4,
    iterations: int = 100,
    learning_rate: float = 0.1,
    mesh=None,
) -> LogisticRegressionModel:
    """Full-batch multinomial logistic regression.

    With ``mesh``, examples shard over the ``data`` axis (rows padded to
    the axis size with zero-weight samples so the mean is exact) and
    parameters replicate; the gradient's cross-example reductions become
    XLA-inserted psums over ICI -- the Spark-executor data parallelism of
    MLlib's LogisticRegressionWithLBFGS, rebuilt as GSPMD sharding.
    """
    from predictionio_tpu.parallel.mesh import replicated, shard_examples

    x_j, y_j, w_j, mesh = shard_examples(mesh, x, y)
    if mesh is not None:
        rep = replicated(mesh)
        put_params = lambda p: jax.device_put(p, rep)
    else:
        put_params = lambda p: p
    dim = x.shape[1]
    params = put_params({
        "w": jnp.zeros((dim, num_classes), dtype=jnp.float32),
        "b": jnp.zeros((num_classes,), dtype=jnp.float32),
    })

    def loss_fn(p):
        logits = x_j @ p["w"] + p["b"]
        nll = optax.softmax_cross_entropy_with_integer_labels(logits, y_j)
        nll = (nll * w_j).sum() / w_j.sum()
        return nll + reg * (p["w"] ** 2).sum()

    if hasattr(optax, "lbfgs"):
        opt = optax.lbfgs()
        value_and_grad = optax.value_and_grad_from_state(loss_fn)

        def step(p, state):
            value, grad = value_and_grad(p, state=state)
            updates, state = opt.update(
                grad, state, p, value=value, grad=grad, value_fn=loss_fn
            )
            return optax.apply_updates(p, updates), state
    else:  # pragma: no cover - older optax
        opt = optax.adam(learning_rate)

        def step(p, state):
            grad = jax.grad(loss_fn)(p)
            updates, state = opt.update(grad, state, p)
            return optax.apply_updates(p, updates), state

    # ONE dispatch for the whole optimization: a Python loop of jitted
    # steps pays a dispatch and a host->device round trip per iteration
    @jax.jit
    def run(p, state):
        return jax.lax.fori_loop(
            0,
            iterations,
            lambda _, carry: step(*carry),
            (p, state),
        )

    params, _ = run(params, opt.init(params))
    return LogisticRegressionModel(np.asarray(params["w"]), np.asarray(params["b"]))
