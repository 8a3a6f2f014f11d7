"""Compile the main path for a described TPU v5e chip, without the chip.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``jax.experimental.topologies``) and not attached, and raises
what the chip's compiler would raise — unaligned blocks, unsupported
Pallas primitives, programs that do not fit.  Nothing runs, so these
tests say nothing about results or times.

What is compiled, at the paper's full detector width (112-128-64-32
autoencoder, Comms-ML, N=10, 60 rounds, E=3):

* the campaign bucket cores: fused single-model (tolfl + sbt), the fl
  iso-tracking bucket, the multi-model ifca bucket, and an unfused
  per-cell tolfl bucket;
* the serving bucket core at batch buckets 1, 8 and 64;
* the Pallas kernels ``tolfl_combine`` and ``rglru_scan`` in compiled
  (not interpret) mode;
* at the CIFAR-10 width (3072 features, 1313 rows a device, so the last
  row tile is partial): the fl iso-tracking bucket, whose loss takes the
  fused output-layer kernel ``recon_out``, and that kernel alone under
  the bucket's two vmaps.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the suite runs under
several xdist workers that all import this file.  The persistent
compilation cache is off around the compiles (an entry written for a
described chip cannot be read back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.datasets import base_config, data_spec, prepare
from repro.api import (NO_FAILURE, CellSpec, DataSpec, ExperimentSpec,
                       FailureSpec, SeedSpec, SimConfig, TraceSpec, plan)
from repro.configs.autoencoder_paper import CIFAR10
from repro.core import campaign, experiment
from repro.models.detector import as_detector
from repro.serving.anomaly import engine

ROUNDS = 60


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def commsml():
    return prepare("commsml", seed=0)


def _spec(prep, cells, fuse=True):
    return ExperimentSpec(
        data=data_spec(prep), base=base_config(prep, ROUNDS), cells=cells,
        traces=TraceSpec.explicit(NO_FAILURE,
                                  FailureSpec(ROUNDS // 2, "server")),
        seeds=SeedSpec((0, 1)), fuse=fuse)


BUCKETS = {
    # name: (cells, fuse, expected (kind, fused, track_iso))
    "tolfl_sbt_fused": ((CellSpec("tolfl", 2), CellSpec("sbt", 10)), True,
                        ("single", True, False)),
    "fl_iso": ((CellSpec("fl", 1),), True, ("single", True, True)),
    "ifca_multi": ((CellSpec("ifca", 2),), True, ("multi", True, False)),
    "tolfl_per_cell": ((CellSpec("tolfl", 2),), False,
                       ("single", False, False)),
}


@pytest.mark.parametrize("name", sorted(BUCKETS))
def test_campaign_bucket_compiles_for_v5e(name, commsml, one_chip):
    cells, fuse, expect = BUCKETS[name]
    p = plan(_spec(commsml, cells, fuse))
    assert len(p.buckets) == 1
    b = p.buckets[0]
    assert (b.kind, b.fused, b.track_iso) == expect
    key = campaign._exe_key(*experiment._bucket_exe_args(p.spec.data, b))
    avals = experiment._bucket_avals(p.spec.data, b,
                                     [p.cells[i] for i in b.cell_indices])
    compiled = campaign._build_executable(*key).lower(
        *_on(one_chip, avals)).compile(
            compiler_options=campaign._AOT_COMPILER_OPTIONS)
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("bs", [1, 8, 64])
def test_serving_bucket_compiles_for_v5e(bs, commsml, one_chip):
    det = as_detector(commsml.ae_cfg)
    n_rows = commsml.device_x.shape[0] + 1          # global + N isolated
    params = jax.eval_shape(det.init_params, jax.random.PRNGKey(0))
    rows = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_rows,) + s.shape, s.dtype),
        params)
    avals = (rows, jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((bs, 8, commsml.device_x.shape[-1]),
                                  jnp.float32))
    engine._jitted_score(commsml.ae_cfg).lower(
        *_on(one_chip, avals)).compile(
            compiler_options=campaign._AOT_COMPILER_OPTIONS)


def test_tolfl_combine_compiles_for_v5e(commsml, one_chip):
    from repro.kernels.tolfl_combine import tolfl_combine
    n_params = as_detector(commsml.ae_cfg).param_count()
    gs = jax.ShapeDtypeStruct((2, n_params), jnp.float32, sharding=one_chip)
    ns = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(tolfl_combine).lower(gs, ns).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_scan_compiles_for_v5e(one_chip):
    from repro.kernels.rglru_scan import rglru_scan
    a = jax.ShapeDtypeStruct((8, 256, 512), jnp.float32, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((8, 512), jnp.float32, sharding=one_chip)
    compiled = jax.jit(rglru_scan).lower(a, a, h0).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the cifar10-ae benchmark configuration's device shape
CIFAR_ROWS, CIFAR_DEVICES = 1313, 10


@pytest.fixture
def tensorfloat32():
    """The benchmark's matmul precision: three bfloat16 passes."""
    with jax.default_matmul_precision("tensorfloat32"):
        yield


def test_cifar_fl_bucket_compiles_with_fused_output_layer(one_chip,
                                                          tensorfloat32):
    width = CIFAR10.input_dim
    data = DataSpec(
        model=CIFAR10,
        device_x=np.zeros((CIFAR_DEVICES, CIFAR_ROWS, width), np.float32),
        device_counts=np.full((CIFAR_DEVICES,), CIFAR_ROWS),
        test_x=np.zeros((512, width), np.float32),
        test_y=np.arange(512) % 2)
    p = plan(ExperimentSpec(
        data=data, cells=(CellSpec("fl", 1),),
        base=SimConfig(num_devices=CIFAR_DEVICES, rounds=ROUNDS, lr=1e-3,
                       local_epochs=5),
        traces=TraceSpec.explicit(NO_FAILURE,
                                  FailureSpec(ROUNDS // 2, "server")),
        seeds=SeedSpec((0, 1))))
    (b,) = p.buckets
    assert (b.kind, b.track_iso) == ("single", True)
    key = campaign._exe_key(*experiment._bucket_exe_args(p.spec.data, b))
    avals = experiment._bucket_avals(p.spec.data, b,
                                     [p.cells[i] for i in b.cell_indices])
    text = campaign._build_executable(*key).lower(
        *_on(one_chip, avals)).compile(
            compiler_options=campaign._AOT_COMPILER_OPTIONS).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # no (scenario, device, row, feature) float32 array anywhere: x is
    # not broadcast to the scenarios, x_hat and its error stay in VMEM
    assert not re.search(rf"f32\[\d+,{CIFAR_DEVICES},{CIFAR_ROWS},{width}\]",
                         text)


def test_recon_out_compiles_for_v5e(one_chip, tensorfloat32):
    from repro.kernels.recon_out import passes, recon_out
    S, N, R, H, F = 4, CIFAR_DEVICES, CIFAR_ROWS, 128, CIFAR10.input_dim

    def step(h, w, b, x, valid):
        return recon_out(h, w, b, x, valid, grads=True, n_passes=passes())
    shapes = [(S, N, R, H), (S, N, H, F), (S, N, F), (N, R, F), (N, R)]
    compiled = jax.jit(jax.vmap(jax.vmap(step),
                                in_axes=(0, 0, 0, None, None))).lower(
        *(jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
          for s in shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
