"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.congestion import congestion_scan
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


# --------------------------------------------------------------------------- #
# congestion kernel (the paper's hot loop)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("n", [7, 100, 2048, 5000])
@pytest.mark.parametrize("stt", [0.1, 7.5, 100.0])
@pytest.mark.parametrize("frac", [0.0, 0.5, 1.0])
def test_congestion_kernel_matches_ref(n, stt, frac):
    rng = np.random.default_rng(n)
    t = np.sort(rng.uniform(0, 1e5, n)).astype(np.float32)
    m = rng.random(n) < frac
    start, delay = congestion_scan(jnp.asarray(t), jnp.asarray(m), stt, interpret=True)
    want = ref.serial_queue(jnp.asarray(t), jnp.asarray(m), stt)
    np.testing.assert_allclose(np.asarray(start), np.asarray(want), rtol=1e-6, atol=1e-3)
    assert (np.asarray(delay) >= -1e-3).all()


def test_congestion_kernel_block_boundary_carry():
    """Carry across grid steps: saturated queue spanning many blocks."""
    n, stt = 4096 + 3, 10.0
    t = np.zeros((n,), np.float32)  # all arrive at once -> pure serial queue
    m = np.ones((n,), bool)
    start, _ = congestion_scan(jnp.asarray(t), jnp.asarray(m), stt, block=1024, interpret=True)
    np.testing.assert_allclose(np.asarray(start), np.arange(n) * stt, rtol=1e-5)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #

ATTN_CASES = [
    # B, H, Hk, Sq, Sk, D, causal, qoff
    (1, 4, 2, 256, 256, 64, True, 0),
    (2, 8, 2, 128, 128, 32, False, 0),
    (1, 2, 2, 128, 512, 64, True, 384),  # decode tail with cache
    (1, 16, 8, 512, 512, 128, True, 0),
    (2, 4, 4, 256, 256, 128, True, 0),  # MHA (no GQA)
]


@pytest.mark.parametrize("case", ATTN_CASES, ids=[str(c) for c in ATTN_CASES])
def test_flash_attention_matches_ref(case):
    B, H, Hk, Sq, Sk, D, causal, qoff = case
    ks = jax.random.split(jax.random.PRNGKey(B * Sq + D), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hk, Sk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hk, Sk, D), jnp.float32)
    o = flash_attention(
        q, k, v, q_offset=qoff, causal=causal, block_q=128, block_k=128, interpret=True
    )
    w = ref.mha_attention(q, k, v, causal=causal, q_offset=qoff)
    np.testing.assert_allclose(np.asarray(o), np.asarray(w), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), dtype)
    o = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    w = ref.mha_attention(q, k, v)
    assert o.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(w, np.float32), rtol=tol, atol=tol
    )


def test_chunked_attention_matches_ref_nondivisible():
    from repro.models.attention import chunked_attention

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 4, 200, 32))
    k = jax.random.normal(ks[1], (2, 2, 200, 32))
    v = jax.random.normal(ks[2], (2, 2, 200, 32))
    o = chunked_attention(q, k, v, causal=True, block_q=128, block_k=128)
    w = ref.mha_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(w), rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# SSD scan
# --------------------------------------------------------------------------- #

SSD_CASES = [
    # B, L, H, P, N, chunk
    (2, 256, 4, 32, 16, 64),
    (1, 128, 2, 64, 128, 128),
    (1, 512, 8, 16, 32, 128),
    (2, 64, 1, 8, 8, 32),
]


@pytest.mark.parametrize("case", SSD_CASES, ids=[str(c) for c in SSD_CASES])
def test_ssd_kernel_matches_naive(case):
    B, L, H, P, N, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(L + H), 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, L, N)) * 0.5
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    w = ref.ssd_naive(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(w), rtol=2e-4, atol=2e-4)


def test_ssd_chunked_ref_matches_naive():
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    B, L, H, P, N = 2, 128, 4, 16, 8
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, L, N)) * 0.5
    y = ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    w = ref.ssd_naive(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(w), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# ops dispatch layer
# --------------------------------------------------------------------------- #


def test_ops_dispatch_modes_agree():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    a = ops.attention(q, k, v, impl="ref")
    b = ops.attention(q, k, v, impl="pallas_interpret", block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    assert ops.get_implementation() in ("ref", "pallas", "pallas_interpret")
    with pytest.raises(ValueError):
        ops.set_implementation("nope")


def test_ops_default_is_ref_and_ssd_pallas_raises():
    """No platform probe picks a kernel: the default is the jnp path, and
    the SSD kernel — which Mosaic refuses — raises instead of falling back."""
    assert ops._IMPL == "ref"
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (1, 64, 2, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 64, 2))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (2,)) * 0.3)
    Bm = jax.random.normal(ks[3], (1, 64, 8)) * 0.5
    Cm = jax.random.normal(ks[4], (1, 64, 8)) * 0.5
    with pytest.raises(NotImplementedError, match="impl='ref'"):
        ops.ssd(x, dt, A, Bm, Cm, chunk=32, impl="pallas")
    np.testing.assert_allclose(
        np.asarray(ops.ssd(x, dt, A, Bm, Cm, chunk=32, impl="pallas_interpret")),
        np.asarray(ops.ssd(x, dt, A, Bm, Cm, chunk=32)),
        rtol=2e-4, atol=2e-4,
    )
