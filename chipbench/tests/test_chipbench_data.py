"""The benchmark's copies of the program's data generators and failure
draws give, at a fixed seed, the arrays the program's own give."""
import numpy as np
import pytest

from cbench import data, failures


@pytest.mark.parametrize("seed", [0, 5])
def test_commsml_copy_matches_program(seed):
    from repro.data import commsml
    X, y = commsml.generate(seed=seed, samples_per_class=90)
    Xc, yc = data.commsml(seed, 90)
    np.testing.assert_array_equal(Xc, X)
    np.testing.assert_array_equal(yc, y)


def test_reference_images_copy_matches_program():
    """The program seeds with ``seed + hash(name) % 65536`` (salted per
    process); handing the copy that same integer gives the same data."""
    from repro.data import reference
    X, y = reference.generate("cifar10", seed=3, samples_per_class=12)
    Xc, yc = data.reference_images("cifar10", 3 + hash("cifar10") % 65536,
                                   12)
    np.testing.assert_allclose(Xc, X, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(yc, y)


def test_reference_seed_is_stable():
    assert data.reference_seed("cifar10", 4) == data.reference_seed(
        "cifar10", 4)
    assert data.reference_seed("cifar10", 4) == 4 + 718294591 % 65536


@pytest.mark.parametrize("clusters,anomaly", [(2, [2, 3]), (5, [3])])
def test_split_and_pad_copy_matches_program(clusters, anomaly):
    from repro.data import federated
    X, y = data.commsml(1, 50)
    s = federated.make_split(X, y, 10, clusters, anomaly, seed=9)
    c = data.make_split(X, y, 10, clusters, anomaly, seed=9)
    np.testing.assert_array_equal(c.test_x, s.test_x)
    np.testing.assert_array_equal(c.test_y, s.test_y)
    for a, b in zip(federated.pad_devices(s), data.pad_devices(c)):
        np.testing.assert_array_equal(b, a)


def _as_np(t):
    return {f: np.asarray(getattr(t, f))
            for f in ("epochs", "devices", "alive_after", "kinds")}


@pytest.mark.parametrize("k,p", [(2, 0.3), (1, 0.5), (10, 0.2), (5, 0.9)])
def test_iid_draws_match_program(k, p):
    from repro.core.failure import sample_traces
    from repro.core.topology import Topology
    want = sample_traces(np.random.default_rng(11), Topology(10, k), p,
                         max_events=20, rounds=60, num_traces=4)
    rng = np.random.default_rng(11)
    for t in want:
        got = failures.iid_trace(rng, 10, k, p, 60, 20)
        for f, v in _as_np(t).items():
            np.testing.assert_array_equal(got[f], v)


@pytest.mark.parametrize("kind,k", [("none", 2), ("server", 2),
                                    ("client", 2), ("client", 1),
                                    ("client", 10), ("server", 10)])
def test_single_events_match_program(kind, k):
    from repro.core.failure import FailureSpec, FailureTrace
    from repro.core.topology import Topology
    want = FailureTrace.from_spec(FailureSpec(30, kind), Topology(10, k), 20)
    got = failures.single_event(kind, 30, 10, k, 20)
    for f, v in _as_np(want).items():
        np.testing.assert_array_equal(got[f], v)


def test_alive_rounds_matches_program_mask():
    import jax.numpy as jnp
    from repro.core.failure import FailureTrace, trace_alive_mask
    rng = np.random.default_rng(3)
    for _ in range(5):
        t = failures.iid_trace(rng, 10, 2, 0.5, 40, 20)
        ft = FailureTrace(*(jnp.asarray(t[f]) for f in (
            "epochs", "devices", "alive_after", "kinds")))
        ref = failures.alive_rounds(t, 10, 40)
        for r in range(40):
            np.testing.assert_array_equal(
                ref[r], np.asarray(trace_alive_mask(ft, 10, jnp.int32(r))))


def test_cascade_heads_down_for_exactly_the_lag():
    rng = np.random.default_rng(0)
    for _ in range(20):
        t = failures.cascade_trace(rng, 10, 2, 400, 20, 1.0, 0.5, 1.0,
                                   100, 5)
        alive = failures.alive_rounds(t, 10, 400)
        for head in (0, 5):
            assert (alive[:, head] == 0).sum() == 100
