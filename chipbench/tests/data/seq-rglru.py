"""The program's windowed RG-LRU sequence detector as a campaign body
(``"kind": "seq-rglru"``), for the test that a body enters the
benchmark by its file alone (``test_chipbench_bodies.py`` copies this
file into a body directory of its own).

Model keys: ``input_dim``, ``window``, ``d_model``, ``conv1d_width``,
``dropout`` and ``act`` (``gelu``); the recurrence is as wide as
``d_model``.

The plain reference imports nothing of the program.  A row of
``input_dim`` features, zero-padded to a whole number of windows, is
read as a sequence of ``S = ceil(input_dim / window)`` steps of
``window`` values.  Each step is embedded (dense + tanh-approximated
GELU) and run through one Griffin recurrent block:

    g_t = gelu(W_g u_t)                      (gate branch)
    v_t = conv_t(W_v u)                      (causal depthwise conv)
    r_t = sigmoid(A v_t),  i_t = sigmoid(X v_t)
    a_t = exp(8 r_t log sigmoid(lambda))
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) (i_t v_t),  h_{-1} = 0
    y_t = W_o (h_t g_t)

with the recurrence written as a loop over time.  Inverted dropout at
the model's rate acts on y while training, drawn from the step's key
itself; a linear decoder maps each step back to its window.  The
anomaly score of a row is its squared reconstruction error over the
``input_dim`` features.  Initial weights follow the program's draws:
dense kernels N(0, 1/fan_in) (the gates' N(0, 0.02^2), the conv's
N(0, 0.02^2)), zero biases, sigmoid(lambda) uniform in [0.9, 0.999].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from cbench.reference import matmul


def program_model(model: dict):
    from repro.api import SeqDetector
    return SeqDetector(
        input_dim=int(model["input_dim"]), window=int(model["window"]),
        d_model=int(model["d_model"]),
        conv1d_width=int(model["conv1d_width"]),
        dropout=float(model["dropout"]), act=model["act"])


def program_loss_class():
    from repro.api import SeqDetector
    return SeqDetector


def program_params(params: dict, model: dict) -> dict:
    return params


def _normal(key, shape, stddev):
    return (jax.random.normal(key, shape) * stddev).astype(jnp.float32)


def init(key, model: dict) -> dict:
    if model["act"] != "gelu":
        raise ValueError(f"the reference embeds with GELU, the model asks "
                         f"for {model['act']!r}")
    win, d, cw = (int(model[k]) for k in ("window", "d_model",
                                          "conv1d_width"))
    k_enc, k_core, k_dec = jax.random.split(key, 3)
    ks = jax.random.split(k_core, 7)
    u = jax.random.uniform(ks[5], (d,), minval=0.9, maxval=0.999)
    return {
        "enc": {"w": _normal(k_enc, (win, d), 1.0 / math.sqrt(win)),
                "b": jnp.zeros((d,), jnp.float32)},
        "rglru": {
            "in_x": {"w": _normal(ks[0], (d, d), 1.0 / math.sqrt(d))},
            "in_gate": {"w": _normal(ks[1], (d, d), 1.0 / math.sqrt(d))},
            "conv_w": _normal(ks[2], (cw, d), 0.02),
            "conv_b": jnp.zeros((d,), jnp.float32),
            "gate_a": {"w": _normal(ks[3], (d, d), 0.02)},
            "gate_x": {"w": _normal(ks[4], (d, d), 0.02)},
            "lam": jnp.log(u / (1 - u)).astype(jnp.float32),
            "out": {"w": _normal(ks[6], (d, d), 1.0 / math.sqrt(d))}},
        "dec": {"w": _normal(k_dec, (d, win), 1.0 / math.sqrt(d)),
                "b": jnp.zeros((win,), jnp.float32)},
    }


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _reconstruct(params, x, precision: str, model: dict, key=None):
    win = int(model["window"])
    steps = -(-int(model["input_dim"]) // win)
    rows = x.shape[0]
    u = jnp.pad(x, ((0, 0), (0, steps * win - x.shape[1]))).reshape(
        rows, steps, win)
    p, r = params, params["rglru"]
    u = _gelu(matmul(u, p["enc"]["w"], precision) + p["enc"]["b"])
    gate = _gelu(matmul(u, r["in_gate"]["w"], precision))
    v_in = matmul(u, r["in_x"]["w"], precision)
    cw = r["conv_w"].shape[0]
    v = []
    for t in range(steps):
        acc = r["conv_b"]
        for j in range(cw):
            s = t - (cw - 1) + j
            if s >= 0:
                acc = acc + v_in[:, s] * r["conv_w"][j]
        v.append(acc)
    v = jnp.stack(v, axis=1)
    gr = jax.nn.sigmoid(matmul(v, r["gate_a"]["w"], precision))
    gi = jax.nn.sigmoid(matmul(v, r["gate_x"]["w"], precision))
    a = jnp.exp(8.0 * gr * jax.nn.log_sigmoid(r["lam"]))
    b = jnp.sqrt(jnp.clip(1.0 - a * a, 1e-12, None)) * (gi * v)
    h = jnp.zeros((rows, v.shape[-1]), jnp.float32)
    hs = []
    for t in range(steps):
        h = a[:, t] * h + b[:, t]
        hs.append(h)
    y = matmul(jnp.stack(hs, axis=1) * gate, r["out"]["w"], precision)
    rate = float(model["dropout"])
    if key is not None and rate > 0:
        keep = jax.random.bernoulli(key, 1.0 - rate, y.shape)
        y = jnp.where(keep, y / (1.0 - rate), 0.0)
    out = matmul(y, p["dec"]["w"], precision) + p["dec"]["b"]
    return out.reshape(rows, -1)[:, :int(model["input_dim"])]


def scores(params, x, precision: str, model: dict):
    """Squared reconstruction error per row."""
    return jnp.sum(jnp.square(x - _reconstruct(params, x, precision,
                                                model)), axis=-1)


def train_loss(params, x, valid, key, precision: str, model: dict):
    err = jnp.sum(jnp.square(x - _reconstruct(params, x, precision, model,
                                               key)), axis=-1)
    return jnp.sum(err * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def _per_step_weights(model: dict) -> int:
    """Weights that each step of a row multiplies: the embedding and the
    decoder, the block's five d x d kernels and the conv taps."""
    win, d, cw = (int(model[k]) for k in ("window", "d_model",
                                          "conv1d_width"))
    return 2 * win * d + 5 * d * d + cw * d


def param_count(model: dict) -> int:
    """The weights above plus the biases and lambda."""
    return (_per_step_weights(model) + 3 * int(model["d_model"])
            + int(model["window"]))


def train_flops_per_scenario(model: dict, counts, local_steps: int,
                             rounds: int) -> float:
    """6 x the weights each step of a row multiplies x steps x real rows
    x local steps x rounds (the recurrence's elementwise work is not
    counted)."""
    steps = -(-int(model["input_dim"]) // int(model["window"]))
    rows = int(sum(int(c) for c in counts))
    return (6.0 * _per_step_weights(model) * steps * rows * local_steps
            * rounds)
