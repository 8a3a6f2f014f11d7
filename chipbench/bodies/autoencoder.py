"""The paper's fully connected autoencoder as a campaign body
(``"kind": "autoencoder"``).

Model keys: ``input_dim``, ``hidden`` (encoder widths), ``code_dim``,
``act`` (``relu``) and ``dropout``.  The widths run input -> hidden ->
code -> reversed hidden -> input.

The plain reference (:func:`init`, :func:`train_loss`, :func:`scores`)
imports nothing of the program: ReLU hidden layers, a linear output,
inverted dropout at the model's rate on every hidden layer while
training (layer i folds i into the step's key); initial weights
N(0, 1/fan_in), zero biases; the anomaly score of a row is its squared
reconstruction error.  Every product goes through
:func:`cbench.reference.matmul`.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp

from cbench.reference import matmul


# ---------------------------------------------------------------------------
# The program's side
# ---------------------------------------------------------------------------
def program_model(model: dict):
    """What ``DataSpec(model=...)`` is given: the program's
    ``AutoencoderConfig``."""
    from repro.api import AutoencoderConfig
    return AutoencoderConfig(
        input_dim=int(model["input_dim"]), hidden=tuple(model["hidden"]),
        code_dim=int(model["code_dim"]), dropout=float(model["dropout"]),
        act=model["act"])


def program_loss_class():
    """The program class whose ``loss`` the ``half_batch`` fault wraps."""
    from repro.models.detector import AutoencoderDetector
    return AutoencoderDetector


def program_params(params: List[Dict], model: dict) -> dict:
    """Parameters in the reference's layout (a list of layers) as the
    program's pytree (``fc<i>`` per layer); leaves are shared."""
    return {f"fc{i}": dict(layer) for i, layer in enumerate(params)}


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------
def layer_dims(model: dict) -> List[int]:
    """Widths input -> hidden -> code -> reversed hidden -> input."""
    hidden = list(model["hidden"])
    return ([model["input_dim"]] + hidden + [model["code_dim"]]
            + hidden[::-1] + [model["input_dim"]])


def init(key, model: dict) -> List[Dict[str, jax.Array]]:
    if model["act"] != "relu":
        raise ValueError(f"the reference has ReLU hidden layers, the "
                         f"model asks for {model['act']!r}")
    dims = layer_dims(model)
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": (jax.random.normal(ks[i], (dims[i], dims[i + 1]))
                   * (1.0 / math.sqrt(dims[i]))).astype(jnp.float32),
             "b": jnp.zeros((dims[i + 1],), jnp.float32)}
            for i in range(len(dims) - 1)]


def forward(params, x, precision: str, dropout_key=None,
            rate: float = 0.0):
    h = x
    for i, layer in enumerate(params):
        h = matmul(h, layer["w"], precision) + layer["b"]
        if i < len(params) - 1:
            h = jnp.maximum(h, 0.0)
            if dropout_key is not None and rate > 0:
                keep = jax.random.bernoulli(
                    jax.random.fold_in(dropout_key, i), 1.0 - rate,
                    h.shape)
                h = jnp.where(keep, h / (1.0 - rate), 0.0)
    return h


def scores(params, x, precision: str, model: dict):
    """Squared reconstruction error per row."""
    return jnp.sum(jnp.square(x - forward(params, x, precision)), axis=-1)


def train_loss(params, x, valid, key, precision: str, model: dict):
    """Mean squared reconstruction error over the valid rows, with
    dropout at the model's rate."""
    err = jnp.sum(jnp.square(x - forward(params, x, precision, key,
                                         float(model["dropout"]))),
                  axis=-1)
    return jnp.sum(err * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def bank(key, model: dict, rows: int) -> List[Dict[str, jax.Array]]:
    """``rows`` scoring-service models in the reference's layout, each
    leaf with a leading ``rows`` axis: N(0, 1/fan_in) weights and
    N(0, 0.1^2) biases, layer i drawn from ``fold_in(key, i)``."""
    dims = layer_dims(model)
    out = []
    for i in range(len(dims) - 1):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        out.append({"w": jax.random.normal(kw, (rows, dims[i], dims[i + 1]))
                    * (1.0 / math.sqrt(dims[i])),
                    "b": 0.1 * jax.random.normal(kb, (rows, dims[i + 1]))})
    return out


# ---------------------------------------------------------------------------
# Operation counts
# ---------------------------------------------------------------------------
def param_count(model: dict) -> int:
    """Weights and biases of the fully connected autoencoder."""
    d = layer_dims(model)
    return sum(a * b + b for a, b in zip(d[:-1], d[1:]))


def train_flops_per_scenario(model: dict, counts, local_steps: int,
                             rounds: int) -> float:
    """Forward and backward FLOPs of one scenario's local training:
    6 x parameters x real rows x local steps x rounds.  Test scoring and
    padded rows are not counted."""
    rows = int(sum(int(c) for c in counts))
    return 6.0 * param_count(model) * rows * local_steps * rounds


def kernel_counts(model: dict, geometry: dict) -> Dict[str, dict]:
    """FLOPs and HBM bytes of one call of each kernel, by op-name
    prefix, for one local step of a single-model bucket:
    ``geometry`` = ``{"kind", "scenarios", "devices", "rows"}``, the
    scenarios and devices one call batches and the padded rows of a
    device (padded rows count: the kernel runs them).

    ``recon_out`` (the output layer, its loss and its backward) per
    scenario and device: 3 dots of 2 x rows x H x F FLOPs (x_hat = hW,
    dW = h^T d, dh = d W^T); it reads W, b, h and the row weights and
    writes dW, db, dh and the loss's (1, 128) tile; it reads each
    device's x (rows x F) once per call, since x is the same for every
    scenario.  H is the first hidden width, F the input width; float32
    throughout.  Nothing (``{}``) where the program says its loss does
    not take the kernel (``fuses_output_layer``: by width and matmul
    precision), and for multi-model buckets, which are not counted."""
    if (geometry["kind"] != "single" or not program_loss_class()(
            program_model(model)).fuses_output_layer()):
        return {}
    f, h = int(model["input_dim"]), int(model["hidden"][0])
    s, n, r = (int(geometry[k]) for k in ("scenarios", "devices", "rows"))
    per_unit = 2 * h * f + 2 * f + 2 * r * h + r + 128
    return {"recon_out": {"flops": 3 * 2 * r * h * f * s * n,
                          "bytes": 4 * (n * r * f + s * n * per_unit)}}
