"""Operation counts computed from a configuration's shapes."""
from __future__ import annotations

from typing import List


def layer_dims(model: dict) -> List[int]:
    """Widths input -> hidden -> code -> reversed hidden -> input."""
    hidden = list(model["hidden"])
    return ([model["input_dim"]] + hidden + [model["code_dim"]]
            + hidden[::-1] + [model["input_dim"]])


def param_count(model: dict) -> int:
    """Weights and biases of the fully connected autoencoder."""
    d = layer_dims(model)
    return sum(a * b + b for a, b in zip(d[:-1], d[1:]))


def train_rows(counts) -> int:
    """Real (unpadded) training rows over all devices."""
    return int(sum(int(c) for c in counts))


def train_flops_per_scenario(model: dict, counts, local_steps: int,
                             rounds: int) -> float:
    """Forward and backward FLOPs of one scenario's local training:
    6 x parameters x real rows x local steps x rounds.  Test scoring and
    padded rows are not counted."""
    return 6.0 * param_count(model) * train_rows(counts) * local_steps \
        * rounds
