"""Cache-semantics unit tests.

* Serving caches: slot validity, ring wraps, pad_cache alignment.
* Campaign executable cache: the canonical lru key
  (``campaign._exe_key``) must normalise redundant call spellings to
  ONE entry (no duplicate compiles) while keeping every degree of
  freedom that changes the lowered program distinct (no stale-program
  collisions), and the AOT layer's abstract-argument signature must
  separate executables per shape/dtype/tree.
* Persistent-cache placement: ``JAX_COMPILATION_CACHE_DIR`` when set,
  else the checkout's fixed ``.repro-cache``.
"""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.autoencoder_paper import AutoencoderConfig
from repro.core import campaign as C
from repro.core import compilecache
from repro.core.baselines import MultiModelConfig
from repro.core.campaign import ExecPlan
from repro.core.simulate import SimConfig
from repro.models.attention import cache_slot_validity
from repro.serving.decode import cache_shape, init_cache, pad_cache


# ---------------------------------------------------------------------------
# cache_slot_validity
# ---------------------------------------------------------------------------
def valid(Sc, pos, window=None):
    return np.asarray(cache_slot_validity(Sc, jnp.int32(pos), window))


def test_fresh_cache_all_invalid():
    np.testing.assert_array_equal(valid(8, 0), np.zeros(8, bool))


def test_partially_filled():
    v = valid(8, 3)
    np.testing.assert_array_equal(v, [1, 1, 1, 0, 0, 0, 0, 0])


def test_full_cache_no_wrap():
    np.testing.assert_array_equal(valid(8, 8), np.ones(8, bool))


def test_wrapped_ring_all_slots_hold_recent():
    """After wrapping, every slot holds one of the last Sc positions."""
    v = valid(8, 13)          # positions 5..12 live in slots 5..12 mod 8
    np.testing.assert_array_equal(v, np.ones(8, bool))


def test_window_excludes_stale():
    # window 4, position 6, Sc 8: slots 2..5 (positions 2..5) valid,
    # positions 0,1 are out of window, slots 6,7 empty
    v = valid(8, 6, window=4)
    np.testing.assert_array_equal(v, [0, 0, 0, 1, 1, 1, 0, 0])


def test_window_ring_steady_state():
    # Sc == window: at any wrapped position exactly window-1 cached
    # positions are in-window (self occupies distance 0)
    for pos in (9, 17, 100):
        v = valid(8, pos, window=8)
        assert v.sum() == 7


# ---------------------------------------------------------------------------
# pad_cache
# ---------------------------------------------------------------------------
def test_pad_cache_extends_attention_only():
    cfg = ARCHS["recurrentgemma-9b"].reduced()   # rec, rec, local pattern
    cache = init_cache(cfg, 2, 16)
    padded = pad_cache(cache, cfg, prompt_len=16, target_len=24)
    for path, leaf in jax.tree_util.tree_flatten_with_path(padded)[0]:
        names = [p.key for p in path if hasattr(p, "key")]
        orig = jax.tree_util.tree_flatten_with_path(cache)[0]
        if names[-1] in ("k", "v"):
            axis = leaf.ndim - 3
            assert leaf.shape[axis] in (24, 16)   # full target or window cap
        if names[-1] in ("h", "conv", "wkv"):
            # recurrent state untouched
            pass


def test_pad_cache_respects_window_cap():
    cfg = ARCHS["recurrentgemma-9b"].reduced()
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, sliding_window=8))
    cache = init_cache(cfg, 1, 8)
    padded = pad_cache(cache, cfg, prompt_len=8, target_len=100)
    for path, leaf in jax.tree_util.tree_flatten_with_path(padded)[0]:
        names = [p.key for p in path if hasattr(p, "key")]
        if names[-1] in ("k", "v"):
            axis = leaf.ndim - 3
            assert leaf.shape[axis] == 8          # stays at the window


def test_pad_cache_rolls_truncated_prompt():
    """A prefill that truncated to the window must be rolled back onto the
    ring invariant (slot i = position mod Sc)."""
    cfg = ARCHS["qwen1.5-0.5b"].reduced()
    # hand-build a cache entry: Sc=4, prompt_len=6 -> slots hold pos 2..5
    # at indices 0..3; ring wants pos p at slot p%4: pos4->0,5->1,2->2,3->3
    k = jnp.arange(2, 6, dtype=jnp.float32).reshape(1, 4, 1, 1)
    cache = {"units": {"l0": {"k": k[None], "v": k[None]}}}
    rolled = pad_cache(cache, cfg, prompt_len=6, target_len=4)
    got = np.asarray(rolled["units"]["l0"]["k"]).ravel()
    np.testing.assert_array_equal(got, [4, 5, 2, 3])


def test_cache_shape_families():
    """Cache entries match family semantics."""
    # dense: k/v per layer
    cs = cache_shape(ARCHS["qwen3-8b"].reduced(), 2, 32)
    leaves = jax.tree.leaves(cs)
    assert all(l.shape[-3] == 32 or l.ndim < 3 for l in leaves
               if hasattr(l, "shape"))
    # ssm: constant-size state
    cs = cache_shape(ARCHS["rwkv6-7b"].reduced(), 2, 32)
    for l in jax.tree.leaves(cs):
        assert 32 not in l.shape[1:]  # no seq-length dim


# ---------------------------------------------------------------------------
# campaign executable cache keys (cache-correctness bugfix satellite)
# ---------------------------------------------------------------------------
AE = AutoencoderConfig(input_dim=8, hidden=(4,), code_dim=2)


def _scfg(scheme="tolfl", k=2):
    return SimConfig(scheme=scheme, num_devices=6, num_clusters=k,
                     rounds=2, dropout=False)


def test_exe_key_spelling_invariance():
    """Omitted trailing defaults and explicit kwargs must land on the
    SAME lru entry — raw ``functools.lru_cache`` would key them apart
    and silently compile the identical program twice."""
    cfg = _scfg()
    a = C._executable("single", AE, cfg, 4, None)
    b = C._executable("single", AE, cfg, 4, None, track_iso=False,
                      fused=False)
    assert a is b


def test_exe_key_static_path_normalises_flags():
    """Static builds (k_pad=None) derive the iso branch from
    ``cfg.scheme`` inside ``_build_core``; a caller flag disagreeing
    with the scheme must not mint a second identical executable."""
    cfg = _scfg("tolfl")
    assert (C._exe_key("single", AE, cfg, None, None, True, True)
            == C._exe_key("single", AE, cfg, None, None, False, False))
    # fl statically builds the isolated-fallback branch: forced on
    kf = C._exe_key("single", AE, _scfg("fl", 1), None, None, False, False)
    assert kf[-2] is True
    # ... and there is no fused-static path
    assert kf[-1] is False
    # object identity through the public wrapper
    assert (C._executable("single", AE, cfg, None, None,
                          track_iso=True, fused=True)
            is C._executable("single", AE, cfg, None, None))


def test_exe_key_multi_normalises_track_iso():
    mcfg = MultiModelConfig(num_devices=6, num_models=2, rounds=2,
                            dropout=False)
    assert (C._exe_key("multi", AE, mcfg, None, None, True, False)
            == C._exe_key("multi", AE, mcfg, None, None, False, False))


def test_exe_key_multi_rejects_k_pad():
    mcfg = MultiModelConfig(num_devices=6, num_models=2, rounds=2,
                            dropout=False)
    with pytest.raises(AssertionError, match="multi-model"):
        C._exe_key("multi", AE, mcfg, 3, None, False, False)


def test_exe_key_distinct_programs_stay_distinct():
    """Every knob that changes the lowered program must stay in the
    key: collapsing any pair would serve a stale/wrong executable."""
    cfg = _scfg()
    keys = [
        C._exe_key("single", AE, cfg, 4, None, False, True),
        C._exe_key("single", AE, cfg, 8, None, False, True),     # k_pad
        C._exe_key("single", AE, cfg, 4, 2, False, True),        # ndev
        C._exe_key("single", AE, cfg, 4, None, True, True),      # iso kind
        C._exe_key("single", AE, cfg, 4, None, False, False),    # op split
        C._exe_key("single", AE, _scfg(k=3), 4, None, False, True),  # cfg
        C._exe_key("single", AE, dataclasses.replace(cfg, rounds=3),
                   4, None, False, True),                        # cfg
    ]
    assert len(set(keys)) == len(keys)


def test_exe_key_shard_degrade_shares_unsharded_entry():
    """``ExecPlan(shard=True)`` on a single-device host degrades to
    ``ndev=None`` — the same cache entry as the unsharded path, never a
    duplicate compile."""
    if jax.local_device_count() > 1:
        pytest.skip("host has multiple devices")
    ndev = ExecPlan(shard=True).resolved_devices(warn=False)
    assert ndev is None
    cfg = _scfg()
    assert (C._exe_key("single", AE, cfg, 4, ndev, False, True)
            == C._exe_key("single", AE, cfg, 4, None, False, True))


def test_avals_signature_separates_executables():
    """The AOT key extension: same canonical key, different chunk
    shapes / dtypes / pytree structure -> different compiled
    executables (and equal avals -> equal signature, so re-planning the
    same spec hits the cache)."""
    sds = jax.ShapeDtypeStruct
    a = (sds((4, 3), jnp.float32),)
    assert C._avals_signature(a) == C._avals_signature(
        (sds((4, 3), jnp.float32),))
    assert C._avals_signature(a) != C._avals_signature(
        (sds((8, 3), jnp.float32),))
    assert C._avals_signature(a) != C._avals_signature(
        (sds((4, 3), jnp.int32),))
    assert C._avals_signature(a) != C._avals_signature(
        ((sds((4, 3), jnp.float32),),))   # same leaves, deeper tree
    # dict pytrees: insertion order must not split the cache
    assert (C._avals_signature({"x": sds((2,), jnp.float32),
                                "y": sds((3,), jnp.int32)})
            == C._avals_signature({"y": sds((3,), jnp.int32),
                                   "x": sds((2,), jnp.float32)}))


# ---------------------------------------------------------------------------
# persistent-cache placement
# ---------------------------------------------------------------------------
def test_default_cache_dir_placement(monkeypatch, tmp_path):
    """The directory comes from ``JAX_COMPILATION_CACHE_DIR`` when set,
    else is ``<repo>/.repro-cache`` — a fixed path, never one made from
    a temporary name or the pid (the path is part of every entry's
    key); JAX's off switch disables it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert compilecache.default_cache_dir() == str(tmp_path / "c")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    d = compilecache.default_cache_dir()
    assert d == os.path.join(repo, ".repro-cache")
    assert d == compilecache.default_cache_dir()
    assert not d.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in d

    prev = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        assert compilecache.default_cache_dir() is None
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
