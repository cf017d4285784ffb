"""Flash-attention Pallas kernels vs the jnp oracle (§Perf H3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import hypothesis, st

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import ref_flash_attention as ref

RNG = jax.random.PRNGKey(11)


@pytest.mark.parametrize("B,H,KV,S,d,causal,win,cap", [
    (2, 4, 2, 64, 32, True, None, None),
    (1, 4, 1, 128, 16, True, 16, None),
    (2, 2, 2, 64, 32, False, None, 5.0),
    (1, 8, 2, 96, 32, True, None, 50.0),
])
def test_forward_matches_oracle(B, H, KV, S, d, causal, win, cap):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, d), jnp.float32)
    pos = jnp.arange(S)
    scale = d ** -0.5
    o = flash_attention(q, k, v, pos, pos, scale, causal, win, cap, 32, True)
    r = ref(q, k, v, pos, pos, scale, causal, win, cap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("causal,win,cap", [(True, None, None),
                                            (True, 16, None),
                                            (True, None, 30.0)])
def test_gradients_match_oracle(causal, win, cap):
    B, H, KV, S, d = 1, 4, 2, 64, 16
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, d), jnp.float32)
    pos = jnp.arange(S)
    scale = d ** -0.5
    f = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, pos, pos, scale, causal, win, cap, 32, True) ** 2)
    fr = lambda q, k, v: jnp.sum(ref(q, k, v, pos, pos, scale, causal, win, cap) ** 2)
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=5e-4, err_msg=nm)


@hypothesis.given(S=st.sampled_from([32, 64, 96]),
                  H=st.sampled_from([2, 4]), KV=st.sampled_from([1, 2]),
                  d=st.sampled_from([16, 32]),
                  dtype=st.sampled_from(["float32", "bfloat16"]),
                  seed=st.integers(0, 2**30))
@hypothesis.settings(max_examples=12, deadline=None)
def test_forward_sweep(S, H, KV, d, dtype, seed):
    B = 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(ks[0], (B, H, S, d), dt)
    k = jax.random.normal(ks[1], (B, KV, S, d), dt)
    v = jax.random.normal(ks[2], (B, KV, S, d), dt)
    pos = jnp.arange(S)
    o = flash_attention(q, k, v, pos, pos, d ** -0.5, True, None, None, 32, True)
    r = ref(q, k, v, pos, pos, d ** -0.5, True, None, None)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_model_flash_path_matches_jnp_path(mesh1, monkeypatch):
    """full_attention with REPRO_FLASH on/off agrees (S > q_chunk)."""
    from repro.core.config import AttentionConfig
    from repro.models import attention as A
    cfg = AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=16)
    d, B, S = 64, 1, 128
    p = A.init_attention(RNG, cfg, d)
    x = jax.random.normal(RNG, (B, S, d), jnp.float32)
    monkeypatch.setenv("REPRO_FLASH", "0")
    y0, _ = A.full_attention(p, x, cfg, positions=jnp.arange(S), q_chunk=32)
    monkeypatch.setenv("REPRO_FLASH", "1")
    y1, _ = A.full_attention(p, x, cfg, positions=jnp.arange(S), q_chunk=32,
                             mesh=mesh1)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-4, atol=1e-5)


def test_context_parallel_flash_matches_single(mesh8):
    """Sequence-sharded (context-parallel) flash ≡ unsharded."""
    from repro.core.config import AttentionConfig
    from repro.models import attention as A
    cfg = AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=16)
    d, B, S = 64, 4, 128
    p = A.init_attention(RNG, cfg, d)
    x = jax.random.normal(RNG, (B, S, d), jnp.float32)
    y1, _ = A.full_attention(p, x, cfg, positions=jnp.arange(S), q_chunk=32)
    y8, _ = A.full_attention(p, x, cfg, positions=jnp.arange(S), q_chunk=32,
                             mesh=mesh8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y8),
                               rtol=1e-4, atol=1e-5)
