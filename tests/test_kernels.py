"""Per-kernel Pallas allclose tests vs the pure-jnp oracles in ref.py.

Each kernel is swept over shapes and dtypes (brief deliverable c); the
kernels run in interpret mode on CPU (the TPU-target path is the same code
with interpret=False).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels import recon_out as ro
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.tolfl_combine import tolfl_combine, tolfl_combine_tree


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_SHAPES = [
    # (B, Sq, H, KVH, D, causal, window)
    (1, 128, 4, 4, 32, True, None),          # MHA causal
    (2, 256, 4, 2, 32, True, None),          # GQA 2:1
    (1, 256, 8, 1, 16, True, None),          # MQA (recurrentgemma kv=1)
    (1, 128, 4, 4, 32, False, None),         # bidirectional (encoder)
    (2, 256, 4, 2, 32, True, 64),            # sliding window
    (1, 512, 2, 2, 64, True, 128),           # longer window
]


@pytest.mark.parametrize("B,S,H,KVH,D,causal,window", ATTN_SHAPES)
def test_flash_attention_matches_reference(B, S, H, KVH, D, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, S, H, D))
    k = rand(ks[1], (B, S, KVH, D))
    v = rand(ks[2], (B, S, KVH, D))
    got = flash_attention(q, k, v, causal=causal, window=window,
                          q_block=64, kv_block=64, interpret=True)
    want = ref.attention_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (1, 128, 4, 2, ), jnp.float32)  # placeholder
    q = rand(ks[0], (1, 128, 4, 32), dtype)
    k = rand(ks[1], (1, 128, 2, 32), dtype)
    v = rand(ks[2], (1, 128, 2, 32), dtype)
    got = flash_attention(q, k, v, causal=True, q_block=64, kv_block=64,
                          interpret=True)
    want = ref.attention_reference(q, k, v, causal=True)
    assert got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("q_block,kv_block", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shapes(q_block, kv_block):
    """Output must be block-shape independent."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (1, 256, 2, 32))
    k = rand(ks[1], (1, 256, 2, 32))
    v = rand(ks[2], (1, 256, 2, 32))
    got = flash_attention(q, k, v, causal=True, q_block=q_block,
                          kv_block=kv_block, interpret=True)
    want = ref.attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_fully_masked_rows_finite():
    """Window smaller than block: early rows see only themselves; no NaNs."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (1, 128, 2, 16))
    k = rand(ks[1], (1, 128, 2, 16))
    v = rand(ks[2], (1, 128, 2, 16))
    got = flash_attention(q, k, v, causal=True, window=1, q_block=64,
                          kv_block=64, interpret=True)
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    want = ref.attention_reference(q, k, v, causal=True, window=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------
LRU_SHAPES = [(1, 64, 8), (2, 128, 16), (1, 256, 64), (3, 128, 32)]


@pytest.mark.parametrize("B,S,W", LRU_SHAPES)
def test_rglru_scan_matches_reference(B, S, W):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    a = jax.nn.sigmoid(rand(ks[0], (B, S, W)))      # decay in (0,1)
    b = rand(ks[1], (B, S, W))
    got = rglru_scan(a, b, t_block=32, w_block=8, interpret=True)
    want = ref.rglru_reference(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_rglru_scan_with_initial_state():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    B, S, W = 2, 64, 16
    a = jax.nn.sigmoid(rand(ks[0], (B, S, W)))
    b = rand(ks[1], (B, S, W))
    h0 = rand(ks[2], (B, W))
    got = rglru_scan(a, b, h0, t_block=16, w_block=16, interpret=True)
    want = ref.rglru_reference(a, b, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_rglru_time_block_carry():
    """State must carry across time blocks: t_block << S."""
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    a = jax.nn.sigmoid(rand(ks[0], (1, 128, 8)))
    b = rand(ks[1], (1, 128, 8))
    small = rglru_scan(a, b, t_block=16, w_block=8, interpret=True)
    big = rglru_scan(a, b, t_block=128, w_block=8, interpret=True)
    np.testing.assert_allclose(np.asarray(small), np.asarray(big),
                               rtol=1e-6, atol=1e-6)


def test_rglru_matches_associative_scan_path():
    """Pallas kernel == the jnp associative_scan the model uses by default."""
    from repro.models.rglru import _lru_scan
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    a = jax.nn.sigmoid(rand(ks[0], (2, 64, 16)))
    b = rand(ks[1], (2, 64, 16))
    got = rglru_scan(a, b, interpret=True)
    want = _lru_scan(a, b, None, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV6 WKV scan
# ---------------------------------------------------------------------------
WKV_SHAPES = [(1, 32, 2, 8), (2, 64, 2, 16), (1, 128, 4, 32)]


@pytest.mark.parametrize("B,S,H,N", WKV_SHAPES)
def test_rwkv6_scan_matches_reference(B, S, H, N):
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    r = rand(ks[0], (B, S, H, N), scale=0.5)
    k = rand(ks[1], (B, S, H, N), scale=0.5)
    v = rand(ks[2], (B, S, H, N), scale=0.5)
    w = jax.nn.sigmoid(rand(ks[3], (B, S, H, N)) + 2.0)   # decay near 1
    u = rand(ks[4], (H, N), scale=0.3)
    s0 = rand(ks[5], (B, H, N, N), scale=0.1)
    y_got, s_got = rwkv6_scan(r, k, v, w, u, s0, t_block=16, interpret=True)
    y_want, s_want = ref.rwkv6_reference(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               rtol=1e-4, atol=1e-4)


def test_rwkv6_time_block_carry():
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    B, S, H, N = 1, 64, 2, 8
    r, k, v = (rand(ks[i], (B, S, H, N), scale=0.5) for i in range(3))
    w = jax.nn.sigmoid(rand(ks[3], (B, S, H, N)) + 2.0)
    u = rand(ks[4], (H, N), scale=0.3)
    s0 = jnp.zeros((B, H, N, N))
    y1, st1 = rwkv6_scan(r, k, v, w, u, s0, t_block=8, interpret=True)
    y2, st2 = rwkv6_scan(r, k, v, w, u, s0, t_block=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), rtol=1e-5,
                               atol=1e-5)


def test_rwkv6_state_chaining():
    """Running two halves with carried state == one full pass (decode
    chunking correctness)."""
    ks = jax.random.split(jax.random.PRNGKey(10), 6)
    B, S, H, N = 1, 64, 2, 8
    r, k, v = (rand(ks[i], (B, S, H, N), scale=0.5) for i in range(3))
    w = jax.nn.sigmoid(rand(ks[3], (B, S, H, N)) + 2.0)
    u = rand(ks[4], (H, N), scale=0.3)
    s0 = rand(ks[5], (B, H, N, N), scale=0.1)
    y_full, s_full = ref.rwkv6_reference(r, k, v, w, u, s0)
    half = S // 2
    y1, s_mid = rwkv6_scan(*(t[:, :half] for t in (r, k, v, w)), u, s0,
                           t_block=16, interpret=True)
    y2, s_end = rwkv6_scan(*(t[:, half:] for t in (r, k, v, w)), u, s_mid,
                           t_block=16, interpret=True)
    np.testing.assert_allclose(np.concatenate([y1, y2], 1),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_end), np.asarray(s_full),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Tol-FL combine kernel
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(
    k=st.integers(1, 8),
    p=st.integers(1, 300),
    seed=st.integers(0, 2 ** 31 - 1),
)
def test_tolfl_combine_matches_reference(k, p, seed):
    rng = np.random.default_rng(seed)
    gs = jnp.asarray(rng.standard_normal((k, p)).astype(np.float32))
    ns = jnp.asarray(rng.uniform(0.1, 50.0, k).astype(np.float32))
    got = tolfl_combine(gs, ns, block=64, interpret=True)
    want = ref.tolfl_combine_reference(gs, ns)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_tolfl_combine_equals_direct_weighted_mean():
    """The kernel realises the k-invariant streaming mean."""
    rng = np.random.default_rng(0)
    gs = rng.standard_normal((5, 1000)).astype(np.float32)
    ns = rng.uniform(1, 10, 5).astype(np.float32)
    got = tolfl_combine(jnp.asarray(gs), jnp.asarray(ns), interpret=True)
    want = (ns / ns.sum()) @ gs
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_tolfl_combine_padding():
    """P not divisible by block: padding must not leak into the result."""
    rng = np.random.default_rng(1)
    gs = jnp.asarray(rng.standard_normal((3, 97)).astype(np.float32))
    ns = jnp.asarray([1.0, 2.0, 3.0])
    got = tolfl_combine(gs, ns, block=64, interpret=True)
    assert got.shape == (97,)
    want = ref.tolfl_combine_reference(gs, ns)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k,p,block", [
    (1, 5, 4096),      # k == 1: the mean is the single gradient
    (1, 257, 64),      # k == 1 with padding (P % block != 0)
    (4, 130, 32),      # non-default block, P % block != 0
    (3, 64, 16),       # non-default block, exact multiple
    (8, 97, 128),      # block > P (clamped to P)
])
def test_tolfl_combine_edge_shapes(k, p, block):
    """P % block != 0 padding, k == 1, and non-default blocks all match
    the streaming reference exactly."""
    rng = np.random.default_rng(k * 1000 + p)
    gs = jnp.asarray(rng.standard_normal((k, p)).astype(np.float32))
    ns = jnp.asarray(rng.uniform(0.1, 50.0, k).astype(np.float32))
    got = tolfl_combine(gs, ns, block=block, interpret=True)
    assert got.shape == (p,)
    want = ref.tolfl_combine_reference(gs, ns)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if k == 1:
        np.testing.assert_allclose(np.asarray(got), np.asarray(gs[0]),
                                   rtol=2e-5, atol=2e-5)


def test_tolfl_combine_all_zero_counts():
    """All clusters dead (every sample count zero): the combine must be
    an exact zero update, not NaN — the failure-masking path."""
    rng = np.random.default_rng(3)
    gs = jnp.asarray(rng.standard_normal((4, 50)).astype(np.float32))
    ns = jnp.zeros((4,), jnp.float32)
    got = np.asarray(tolfl_combine(gs, ns, block=16, interpret=True))
    assert np.all(np.isfinite(got))
    np.testing.assert_array_equal(got, np.zeros(50, np.float32))
    want = np.asarray(ref.tolfl_combine_reference(gs, ns))
    np.testing.assert_array_equal(got, want)


def test_tolfl_combine_partial_zero_counts():
    """Dead clusters are absorbed as no-ops; survivors renormalise."""
    rng = np.random.default_rng(4)
    gs = rng.standard_normal((5, 33)).astype(np.float32)
    ns = np.array([0.0, 2.0, 0.0, 3.0, 0.0], np.float32)
    got = tolfl_combine(jnp.asarray(gs), jnp.asarray(ns), block=8,
                        interpret=True)
    want = (ns[1] * gs[1] + ns[3] * gs[3]) / (ns[1] + ns[3])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                               atol=2e-5)


def test_tolfl_combine_tree():
    rng = np.random.default_rng(2)
    tree = {"w": jnp.asarray(rng.standard_normal((4, 8, 8)).astype(np.float32)),
            "b": jnp.asarray(rng.standard_normal((4, 8)).astype(np.float32))}
    ns = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    got = tolfl_combine_tree(tree, ns, interpret=True)
    for key in ("w", "b"):
        flat = np.asarray(tree[key]).reshape(4, -1)
        want = (np.asarray(ns) / 10.0) @ flat
        np.testing.assert_allclose(np.asarray(got[key]).ravel(), want.ravel(),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused output layer + reconstruction loss
# ---------------------------------------------------------------------------
# Tolerances, as a share of the largest magnitude of each result, by the
# precision the kernel reads from jax_default_matmul_precision:
# * float32: interpret mode runs float32 dots, so only the order of the
#   sums differs from XLA's (measured 1.5e-7): 1e-5;
# * tensorfloat32: three bfloat16 passes drop the lo*lo term, 2^-16 of
#   each product, and round each lo to 8 bits (measured 6.7e-6): 5e-5;
# * bfloat16: one pass rounds both operands to 8 bits, 2^-8 of each
#   product (measured 3.4e-3): 2e-2.
RECON_TOL = {"float32": 1e-5, "tensorfloat32": 5e-5, "bfloat16": 2e-2}
# (rows, width, block_rows, precision): 37 rows in tiles of 16 leave a
# ragged last tile of 5; 40 in tiles of 8 split evenly; None is one tile
RECON_CASES = [(37, 256, 16, "float32"), (40, 256, 8, "float32"),
               (37, 256, None, "float32"), (37, 256, 16, "tensorfloat32"),
               (37, 128, 16, "bfloat16")]


def _recon_inputs(rows, width, S=2, N=3, hid=128):
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    h = jax.nn.relu(rand(ks[0], (S, N, rows, hid)))
    w = rand(ks[1], (S, N, hid, width), scale=0.1)
    b = rand(ks[2], (S, N, width), scale=0.1)
    x = rand(ks[3], (N, rows, width))            # shared by the scenarios
    valid = (jnp.arange(rows)[None, :]
             < jnp.array([rows, rows - 9, 0])[:N, None]).astype(jnp.float32)
    return h, w, b, x, valid


def _per_device(fn):
    """vmap over (scenario, device), x and valid unbatched over scenarios
    as in a campaign bucket."""
    return jax.vmap(jax.vmap(fn), in_axes=(0, 0, 0, None, None))


def _want(h, w, b, x, valid):
    with jax.default_matmul_precision("float32"):
        loss, (dh, dw, db) = jax.value_and_grad(
            ref.recon_out_reference, argnums=(0, 1, 2))(h, w, b, x, valid)
    return loss, dw, db, dh


@pytest.mark.parametrize("rows,width,block_rows,precision", RECON_CASES)
def test_recon_out_matches_grad_of_reference(rows, width, block_rows,
                                             precision):
    args = _recon_inputs(rows, width)

    def fused(h, w, b, x, valid):
        return ro.recon_out(h, w, b, x, valid / jnp.maximum(valid.sum(), 1.0),
                            grads=True, n_passes=ro.passes(),
                            block_rows=block_rows, interpret=True)
    with jax.default_matmul_precision(precision):
        got = _per_device(fused)(*args)
    want = _per_device(_want)(*args)
    for name, g, e in zip(("loss", "dw", "db", "dh"), got, want):
        assert g.shape == e.shape, name
        tol = RECON_TOL[precision] * float(jnp.max(jnp.abs(e)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=0,
                                   atol=tol, err_msg=name)
    # the device with no valid row: loss and gradients exactly zero
    for g in got:
        assert not np.any(np.asarray(g[:, 2]))


def test_recon_out_loss_only():
    h, w, b, x, valid = _recon_inputs(37, 256)

    def fused(grads):
        def f(h, w, b, x, valid):
            return ro.recon_out(h, w, b, x,
                                valid / jnp.maximum(valid.sum(), 1.0),
                                grads=grads, n_passes=6, block_rows=16,
                                interpret=True)
        return _per_device(f)(h, w, b, x, valid)
    only = fused(False)
    assert len(only) == 1
    np.testing.assert_array_equal(np.asarray(only[0]),
                                  np.asarray(fused(True)[0]))


def test_recon_out_layer_differentiates_params_not_data():
    h, w, b, x, valid = _recon_inputs(37, 256)
    h, w, b, x, valid = h[0, 1], w[0, 1], b[0, 1], x[1], valid[1]
    g = jax.grad(ops.recon_out_layer, argnums=(0, 1, 2))(h, w, b, x, valid)
    e = jax.grad(ref.recon_out_reference, argnums=(0, 1, 2))(
        h, w, b, x, valid)
    for gi, ei in zip(g, e):
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ei))
    assert float(ops.recon_out_layer(h, w, b, x, valid)) == float(
        ref.recon_out_reference(h, w, b, x, valid))
    with pytest.raises(NotImplementedError):
        jax.grad(ops.recon_out_layer, argnums=3)(h, w, b, x, valid)


# ---------------------------------------------------------------------------
# ops.py dispatch layer
# ---------------------------------------------------------------------------
def test_ops_attention_backends_agree():
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = rand(ks[0], (1, 128, 4, 32))
    k = rand(ks[1], (1, 128, 2, 32))
    v = rand(ks[2], (1, 128, 2, 32))
    a = ops.attention(q, k, v, backend="pallas")
    b = ops.attention(q, k, v, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


def test_ops_rglru_backends_agree():
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    a_t = jax.nn.sigmoid(rand(ks[0], (1, 64, 16)))
    b_t = rand(ks[1], (1, 64, 16))
    h0 = rand(ks[2], (1, 16))
    x = ops.rglru(a_t, b_t, h0, backend="pallas")
    y = ops.rglru(a_t, b_t, h0, backend="xla")
    np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                               atol=1e-5)
