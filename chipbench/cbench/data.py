"""Data generation for the benchmark's cells, copied from the program.

Copies of ``repro.data.commsml.generate``, ``repro.data.reference.
generate``, ``repro.data.federated.make_split`` / ``pad_devices`` and
``benchmarks.datasets._equalize_scale``, kept here so that the inputs a
cell runs on cannot change when the program changes.  Two departures,
both tested against the originals (``tests/test_chipbench_data.py``):

* ``reference_images`` takes the integer generator seed itself.  The
  original derives it as ``seed + hash(name) % 65536``, and Python salts
  ``hash`` of a string per process, so it draws different data in every
  process; :func:`reference_seed` uses CRC-32 instead.
* ``reference_images`` draws each class's samples in one vectorised
  call.  ``Generator.standard_normal`` fills an array in the order of
  consecutive scalar draws, so the stream is the same; only the float64
  rounding of the 6-term ``tensordot`` may differ in the last place.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# Comms-ML (112-d), copy of repro.data.commsml
# ---------------------------------------------------------------------------
N_FEATURES = 112
N_STATS = 12
N_SIGNAL = N_FEATURES - N_STATS
N_CLASSES = 4

_CLASS_DEFS = {
    0: dict(duty=0.2, rate=10.0, centers=[20, 60], widths=[6, 8],
            powers=[1.0, 0.8]),
    1: dict(duty=0.7, rate=40.0, centers=[20, 60], widths=[6, 8],
            powers=[1.6, 1.3]),
    2: dict(duty=0.9, rate=120.0, centers=[20, 60], widths=[6, 8],
            powers=[1.1, 0.9]),
    3: dict(duty=0.25, rate=12.0, centers=[20, 60, 85], widths=[6, 8, 1.5],
            powers=[1.0, 0.8, 2.2]),
}


def _spectral_template(centers, widths, powers) -> np.ndarray:
    f = np.arange(N_SIGNAL, dtype=np.float64)
    prof = np.full(N_SIGNAL, 0.05)
    for c, w, p in zip(centers, widths, powers):
        prof += p * np.exp(-0.5 * ((f - c) / w) ** 2)
    return prof


def _stats_features(rng, duty, rate, n) -> np.ndarray:
    pkt_rate = rng.gamma(shape=rate, scale=1.0, size=n) / max(rate, 1)
    airtime = np.clip(duty + 0.08 * rng.standard_normal(n), 0, 1)
    rssi_mean = -55 + 8 * airtime + 1.5 * rng.standard_normal(n)
    rssi_std = 2.5 + 1.2 * airtime + 0.3 * rng.standard_normal(n)
    retries = rng.poisson(lam=2 + 8 * duty, size=n).astype(np.float64)
    mcs_lo = np.clip(0.6 - 0.4 * duty + 0.1 * rng.standard_normal(n), 0, 1)
    mcs_hi = 1.0 - mcs_lo
    iat_mean = 1.0 / np.maximum(pkt_rate * rate, 0.3)
    iat_cv = 0.8 + 0.5 * duty + 0.1 * rng.standard_normal(n)
    chan_util = np.clip(airtime + 0.05 * rng.standard_normal(n), 0, 1)
    n_src = np.round(2 + 3 * duty + rng.standard_normal(n) * 0.5)
    noise_floor = -95 + 1.0 * rng.standard_normal(n)
    return np.stack([pkt_rate, airtime, rssi_mean, rssi_std, retries,
                     mcs_lo, mcs_hi, iat_mean, iat_cv, chan_util,
                     n_src, noise_floor], axis=1)


def _commsml_class(cls: int, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed * N_CLASSES + cls + 1)
    d = _CLASS_DEFS[cls]
    prof = _spectral_template(d["centers"], d["widths"], d["powers"])
    fade = rng.lognormal(mean=0.0, sigma=0.25, size=(n, 1))
    bursts = d["duty"] + (1 - d["duty"]) * rng.beta(2, 5, size=(n, 1))
    sig = prof[None, :] * fade * bursts + 0.03 * rng.standard_normal(
        (n, N_SIGNAL))
    if cls == 3:   # hopping narrowband line: row i rolled by hop[i]
        hop = rng.integers(-7, 8, size=n)
        cols = (np.arange(N_SIGNAL)[None, :] - hop[:, None]) % N_SIGNAL
        sig = np.take_along_axis(sig, cols, axis=1)
    stats = _stats_features(rng, d["duty"], d["rate"], n)
    return np.concatenate([stats, sig], axis=1).astype(np.float32)


def commsml(seed: int, samples_per_class: int
            ) -> Tuple[np.ndarray, np.ndarray]:
    """(X (4n, 112) float32 standardised on class 0, y (4n,) int32)."""
    xs = [_commsml_class(c, samples_per_class, seed)
          for c in range(N_CLASSES)]
    ys = [np.full(samples_per_class, c, np.int32) for c in range(N_CLASSES)]
    X = np.concatenate(xs, 0)
    y = np.concatenate(ys, 0)
    mu = X[y == 0].mean(0, keepdims=True)
    sd = X[y == 0].std(0, keepdims=True) + 1e-6
    return ((X - mu) / sd).astype(np.float32), y


# ---------------------------------------------------------------------------
# Reference images (CIFAR-10 shape), copy of repro.data.reference
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RefSpec:
    name: str
    shape: Tuple[int, ...]
    n_classes: int


REF_SPECS = {"cifar10": RefSpec("cifar10", (32, 32, 3), 10)}
COMPONENTS_PER_CLASS = 6


def reference_seed(name: str, seed: int) -> int:
    """The generator seed of ``reference_images`` for a cell seed: the
    original's ``seed + hash(name) % 65536`` with a hash that is the
    same in every process."""
    return seed + zlib.crc32(name.encode()) % 65536


def _class_basis(rng, h: int, w: int, n_comp: int, pool: np.ndarray
                 ) -> np.ndarray:
    idx = rng.choice(len(pool), n_comp, replace=False)
    basis = []
    for kx, ky in pool[idx]:
        ph = rng.uniform(0, 2 * np.pi)
        basis.append(np.cos(2 * np.pi * (kx * np.arange(h)[:, None] / h
                                         + ky * np.arange(w)[None, :] / w)
                            + ph))
    return np.stack(basis)


def reference_images(name: str, rng_seed: int, samples_per_class: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """(X (C n, prod(shape)) float32 standardised, y) from the generator
    seeded with ``rng_seed`` (see :func:`reference_seed`)."""
    spec = REF_SPECS[name]
    n_per = samples_per_class
    rng = np.random.default_rng(rng_seed)
    ch = spec.shape[2] if len(spec.shape) == 3 else 1
    h, w = spec.shape[0], spec.shape[1]
    freqs = np.array([(kx, ky) for kx in range(1, 16) for ky in range(1, 16)])
    rng.shuffle(freqs)
    per_class = max(len(freqs) // spec.n_classes, 1)
    xs, ys = [], []
    for c in range(spec.n_classes):
        lo = (c * per_class) % len(freqs)
        pool = freqs[lo:lo + per_class] if per_class > 1 else \
            freqs[[c % len(freqs)]]
        basis = np.stack([_class_basis(rng, h, w,
                                       min(COMPONENTS_PER_CLASS, len(pool)),
                                       pool)
                          for _ in range(ch)], -1)   # (n_comp, h, w, ch)
        n_comp = basis.shape[0]
        proto = np.tensordot(2.0 + rng.standard_normal(n_comp), basis,
                             axes=1)
        # per sample, in stream order: n_comp amplitudes, one gain, then
        # one noise value per pixel
        z = rng.standard_normal((n_per, n_comp + 1 + h * w * ch))
        amps = 0.5 * z[:, :n_comp]
        gain = 1.0 + 0.1 * z[:, n_comp]
        noise = z[:, n_comp + 1:]
        img = proto.reshape(1, -1) + amps @ basis.reshape(n_comp, -1)
        img = img * gain[:, None] + 0.1 * noise
        xs.append(img.astype(np.float32))
        ys.append(np.full(n_per, c, np.int32))
    X = np.concatenate(xs, 0)
    y = np.concatenate(ys, 0)
    mu, sd = X.mean(0, keepdims=True), X.std(0, keepdims=True) + 1e-6
    return ((X - mu) / sd).astype(np.float32), y


def equalize_scale(X: np.ndarray) -> np.ndarray:
    """Scale a wide dataset so its summed-square loss matches the 112-d
    Comms-ML one (``benchmarks.datasets._equalize_scale``)."""
    return X * np.sqrt(112.0 / X.shape[1])


# ---------------------------------------------------------------------------
# Federated split, copy of repro.data.federated
# ---------------------------------------------------------------------------
@dataclass
class Split:
    device_data: List[np.ndarray]
    test_x: np.ndarray
    test_y: np.ndarray


def make_split(X: np.ndarray, y: np.ndarray, num_devices: int,
               num_clusters: int, anomaly_classes: Sequence[int],
               seed: int = 0, test_frac: float = 0.25) -> Split:
    """Normal classes round-robin over clusters, each cluster's pool
    split equally over its devices; the test set is the held-out normal
    rows followed by every anomaly-class row (label 1)."""
    if num_devices % num_clusters:
        raise ValueError((num_devices, num_clusters))
    rng = np.random.default_rng(seed)
    anomaly = set(anomaly_classes)
    normal = [c for c in sorted(set(y.tolist())) if c not in anomaly]
    per_cluster = num_devices // num_clusters
    pools: List[List[np.ndarray]] = [[] for _ in range(num_clusters)]
    test_norm = []
    for j, c in enumerate(normal):
        idx = np.where(y == c)[0]
        rng.shuffle(idx)
        n_test = int(len(idx) * test_frac)
        test_norm.append(X[idx[:n_test]])
        pools[j % num_clusters].append(X[idx[n_test:]])
    device_data: List[np.ndarray] = []
    for ci in range(num_clusters):
        pool = (np.concatenate(pools[ci], 0) if pools[ci]
                else np.zeros((0, X.shape[1]), X.dtype))
        rng.shuffle(pool)
        device_data.extend(p.astype(np.float32)
                           for p in np.array_split(pool, per_cluster))
    anom = X[np.isin(y, list(anomaly))]
    test_x = np.concatenate(test_norm + [anom], 0).astype(np.float32)
    test_y = np.concatenate([np.zeros(sum(len(t) for t in test_norm)),
                             np.ones(len(anom))]).astype(np.int32)
    return Split(device_data, test_x, test_y)


def pad_devices(split: Split) -> Tuple[np.ndarray, np.ndarray]:
    """(N, n_max, D) zero-padded device rows and the (N,) true counts."""
    n_max = max(len(d) for d in split.device_data)
    dim = split.device_data[0].shape[1]
    out = np.zeros((len(split.device_data), n_max, dim), np.float32)
    cnt = np.zeros((len(split.device_data),), np.int32)
    for i, d in enumerate(split.device_data):
        out[i, :len(d)] = d
        cnt[i] = len(d)
    return out, cnt


@dataclass
class Federation:
    """A configuration's data, made from the cell's seed."""
    device_x: np.ndarray     # (N, n_max, D) float32
    counts: np.ndarray       # (N,) int32
    test_x: np.ndarray       # (T, D) float32
    test_y: np.ndarray       # (T,) int32, 1 = anomalous


def federation(data_cfg: dict, topo_cfg: dict, seed: int) -> Federation:
    """Generate and split one configuration's data from ``seed``."""
    gen = data_cfg["generator"]
    n = int(data_cfg["samples_per_class"])
    if gen == "commsml":
        X, y = commsml(seed, n)
    elif gen in REF_SPECS:
        X, y = reference_images(gen, reference_seed(gen, seed), n)
        if data_cfg.get("equalize_scale"):
            X = equalize_scale(X)
    else:
        raise KeyError(f"unknown data generator {gen!r}")
    split = make_split(X, y, int(topo_cfg["num_devices"]),
                       int(data_cfg["split_clusters"]),
                       data_cfg["anomaly_classes"], seed=seed,
                       test_frac=float(data_cfg["test_frac"]))
    dx, cnt = pad_devices(split)
    return Federation(dx, cnt, split.test_x, split.test_y)
