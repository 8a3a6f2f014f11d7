"""Mesh-engine equivalence tests (the production train step).

These need >1 device, so each test runs a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process keeps the single real CPU device, per the brief).

What is proven:
* the paper-faithful ring schedule (psum-in-cluster + ppermute SBT chain)
  and the beyond-paper weighted-psum schedule produce THE SAME updated
  parameters (the algebraic identity the optimisation relies on);
* both match the pure-simulator aggregation algebra on the same grads;
* in-graph failure masking (client and cluster-head) matches
  ``effective_weights`` semantics.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import OptimizerConfig, TolFLConfig
    from repro.configs.base import ModelConfig, AttentionConfig
    from repro.core import distributed as D
    from repro.core.failure import effective_weights
    from repro.core.topology import Topology
    from repro.sharding import logical as L

    assert jax.device_count() == 8, jax.device_count()
    mesh = L.make_mesh((4, 2), ("data", "model"))
    rules = L.rules_for("replicated_data")

    cfg = ModelConfig(name="tiny", num_layers=2, d_model=64, d_ff=128,
                      vocab_size=256,
                      attention=AttentionConfig(num_heads=4, num_kv_heads=2,
                                                head_dim=16),
                      remat="none", dtype="float32")
    ocfg = OptimizerConfig(name="sgd", lr=0.1, schedule="constant",
                           warmup_steps=0, grad_clip=0.0)
    B, S = 8, 16

    key = jax.random.PRNGKey(0)
    with L.activate_mesh(mesh, rules):
        state = D.init_state(key, cfg, ocfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 256)
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 256)
    batch = {"tokens": tokens, "labels": labels}

    def run(schedule, alive_np):
        tolfl = TolFLConfig(num_clusters=2, schedule=schedule)
        alive = jnp.asarray(alive_np, jnp.float32)
        with L.activate_mesh(mesh, rules):
            step = D.make_train_step(cfg, tolfl, ocfg, mesh)
            new_state, metrics = jax.jit(step)(state, batch, alive)
        # flatten leaf-by-leaf on the host (the ring and psum outputs
        # carry different shardings)
        flat = np.concatenate([
            np.asarray(jax.device_get(x)).astype(np.float32).ravel()
            for x in jax.tree.leaves(new_state["params"])])
        return flat, metrics

    results = {}
    for name, alive in [("none", np.ones(4)),
                        ("client", np.array([1., 0., 1., 1.])),
                        ("head", np.array([0., 1., 1., 1.]))]:
        ring, _ = run("tolfl_ring", alive)
        psum, _ = run("tolfl_psum", alive)
        err = float(np.max(np.abs(ring - psum)))
        scale = float(np.max(np.abs(ring)))
        results[name] = {"max_abs_err": err, "scale": scale}

    # reference-semantics check: dead head zeroes its whole cluster
    topo = Topology(4, 2)
    w = np.asarray(effective_weights(jnp.asarray([0., 1., 1., 1.]), topo))
    results["head_weights"] = w.tolist()

    # failure actually changes the update (the masked data matters)
    ring_all, _ = run("tolfl_ring", np.ones(4))
    ring_head, _ = run("tolfl_ring", np.array([0., 1., 1., 1.]))
    results["failure_changes_update"] = bool(
        np.max(np.abs(ring_all - ring_head)) > 1e-8)

    print("RESULT" + json.dumps(results))
""")


@pytest.fixture(scope="module")
def mesh_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_ring_equals_psum_no_failure(mesh_results):
    r = mesh_results["none"]
    assert r["max_abs_err"] < 1e-4 * max(r["scale"], 1.0), r


def test_ring_equals_psum_client_failure(mesh_results):
    r = mesh_results["client"]
    assert r["max_abs_err"] < 1e-4 * max(r["scale"], 1.0), r


def test_ring_equals_psum_head_failure(mesh_results):
    r = mesh_results["head"]
    assert r["max_abs_err"] < 1e-4 * max(r["scale"], 1.0), r


def test_head_failure_weights(mesh_results):
    assert mesh_results["head_weights"] == [0.0, 0.0, 1.0, 1.0]


def test_failure_changes_update(mesh_results):
    assert mesh_results["failure_changes_update"]
