"""Numerics tests for the beyond-paper perf levers (EXPERIMENTS.md §Perf):

* ``grad_sync_dtype=bfloat16`` — the synced update must stay within bf16
  rounding of the f32-synced update;
* ``microbatches=m`` — gradient accumulation must match the single-batch
  gradient exactly (same data, mean-of-means with equal shards);
* ``param_cast_dtype`` — loss computed off bf16-cast params stays close.

Run in a subprocess with 8 virtual devices (same pattern as
tests/test_distributed.py).
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
    from repro.sharding import logical as L

    mesh = L.make_mesh((4, 2), ("data", "model"))
    rules = L.rules_for("replicated_data")
    cfg = ModelConfig(name="tiny", num_layers=2, d_model=64, d_ff=128,
                      vocab_size=256,
                      attention=AttentionConfig(num_heads=4, num_kv_heads=2,
                                                head_dim=16),
                      remat="none", dtype="float32")
    ocfg = OptimizerConfig(name="sgd", lr=0.1, schedule="constant",
                           warmup_steps=0, grad_clip=0.0)
    with L.activate_mesh(mesh, rules):
        state = D.init_state(jax.random.PRNGKey(0), cfg, ocfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16),
                                          0, 256),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16),
                                          0, 256)}
    alive = jnp.ones(4)

    def run(schedule, **kw):
        tolfl = TolFLConfig(num_clusters=2, schedule=schedule, **kw)
        with L.activate_mesh(mesh, rules):
            step = D.make_train_step(cfg, tolfl, ocfg, mesh)
            new_state, _ = jax.jit(step)(state, batch, alive)
        return np.concatenate([np.asarray(x, np.float32).ravel()
                               for x in jax.tree.leaves(
                                   new_state["params"])])

    base_ring = run("tolfl_ring")
    base_psum = run("tolfl_psum")
    out = {}

    def rel(a, b):
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))

    out["bf16_sync_vs_f32"] = rel(
        run("tolfl_ring", grad_sync_dtype="bfloat16"), base_ring)
    # NB: ring microbatching splits the PER-SHARD batch (2 rows here), so
    # mb=2 is the max for this mesh; psum splits the global batch.
    out["ring_mb2_vs_mb1"] = rel(run("tolfl_ring", microbatches=2),
                                 base_ring)
    out["psum_mb2_vs_mb1"] = rel(run("tolfl_psum", microbatches=2),
                                 base_psum)
    out["psum_mb4_vs_mb1"] = rel(run("tolfl_psum", microbatches=4),
                                 base_psum)
    out["param_cast_vs_f32"] = rel(
        run("tolfl_psum", param_cast_dtype="bfloat16"), base_psum)
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_bf16_grad_sync_close(results):
    """bf16 has ~2^-8 relative precision; the synced update must stay
    within a few rounding steps of the f32 path."""
    assert results["bf16_sync_vs_f32"] < 0.05


def test_microbatch_accumulation_matches(results):
    # mean-of-means == global mean for equal splits; only reduction-order
    # float noise is allowed
    assert results["ring_mb2_vs_mb1"] < 1e-4
    assert results["psum_mb2_vs_mb1"] < 1e-4
    assert results["psum_mb4_vs_mb1"] < 1e-4


def test_param_cast_close(results):
    assert results["param_cast_vs_f32"] < 0.05
