"""AUROC as the campaign reports it, copied from
``repro.training.metrics.auroc_batch``: the Mann-Whitney U statistic
with average ranks for ties, one row per scenario."""
from __future__ import annotations

import numpy as np


def auroc_rows(scores: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """(B,) AUROC of every row of ``scores`` (B, T) against ``labels``
    (T,), 1 = anomalous; higher score = more anomalous."""
    scores = np.asarray(scores, np.float64)
    if scores.ndim != 2:
        raise ValueError(scores.shape)
    labels = np.asarray(labels).ravel().astype(bool)
    B, T = scores.shape
    n_pos = int(labels.sum())
    n_neg = T - n_pos
    if n_pos == 0 or n_neg == 0:
        return np.full(B, np.nan)
    order = np.argsort(scores, axis=1, kind="mergesort")
    srt = np.take_along_axis(scores, order, axis=1)
    pos = np.broadcast_to(np.arange(T, dtype=np.float64), (B, T))
    is_start = np.ones((B, T), bool)
    is_start[:, 1:] = srt[:, 1:] != srt[:, :-1]
    is_end = np.ones((B, T), bool)
    is_end[:, :-1] = is_start[:, 1:]
    start = np.maximum.accumulate(np.where(is_start, pos, 0.0), axis=1)
    end = np.minimum.accumulate(
        np.where(is_end, pos, T - 1.0)[:, ::-1], axis=1)[:, ::-1]
    avg_rank = 0.5 * (start + end) + 1.0
    ranks = np.empty_like(avg_rank)
    np.put_along_axis(ranks, order, avg_rank, axis=1)
    u = ranks[:, labels].sum(axis=1) - n_pos * (n_pos + 1) / 2.0
    return u / (n_pos * n_neg)
