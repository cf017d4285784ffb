"""Hierarchical AllToAll (paper §3.2, Figs. 5–7): functional equivalence
with flat AllToAll + the α–β cost model that captures the paper's win."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import alltoall

RNG = jax.random.PRNGKey(2)


def _run(mesh_model8, fn):
    return jax.jit(jax.shard_map(fn, mesh=mesh_model8, in_specs=P("model"),
                                     out_specs=P("model"), check_vma=False))


@pytest.mark.parametrize("inner,outer", [(2, 4), (4, 2), (8, 1), (1, 8)])
def test_hierarchical_equals_flat(mesh_model8, inner, outer):
    x = jax.random.normal(RNG, (64, 4, 16))     # per-device (8, 4, 16)
    flat = _run(mesh_model8, lambda v: alltoall.flat_all_to_all(v, "model"))
    hier = _run(mesh_model8, lambda v: alltoall.all_to_all(
        v, "model", mode="hierarchical", inner=inner, outer=outer))
    np.testing.assert_allclose(np.asarray(flat(x)), np.asarray(hier(x)),
                               rtol=1e-6)


def test_alltoall_is_involution_on_permutation(mesh_model8):
    """a2a twice returns the original (chunk i->j then j->i)."""
    x = jax.random.normal(RNG, (64, 4, 8))
    f = _run(mesh_model8, lambda v: alltoall.flat_all_to_all(
        alltoall.flat_all_to_all(v, "model"), "model"))
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x), rtol=1e-6)


def test_hierarchical_gradient(mesh_model8):
    x = jax.random.normal(RNG, (64, 4, 8))

    def loss(v):
        out = jax.shard_map(
            lambda u: alltoall.hierarchical_all_to_all(u, "model", inner=4,
                                                       outer=2),
            mesh=mesh_model8, in_specs=P("model"), out_specs=P("model"),
            check_vma=False)(v)
        return jnp.sum(out ** 2)

    g = jax.jit(jax.grad(loss))(x)
    # a2a is a permutation → grad of sum-of-squares is 2x permuted back = 2x
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x), rtol=1e-6)


def test_cost_model_paper_regime():
    """Paper Fig. 7 regime: N nodes × G GPUs, 1 NIC — hierarchical wins
    and the advantage grows with node count (1.66× at 4×8 → 2× at 8×8)."""
    B = 16e6                                      # 16 MB per device (paper)
    s4 = alltoall.cost_flat(B, 4, 8, alltoall.PCIE, alltoall.ETH100) / \
        alltoall.cost_hierarchical(B, 4, 8, alltoall.PCIE, alltoall.ETH100)
    s8 = alltoall.cost_flat(B, 8, 8, alltoall.PCIE, alltoall.ETH100) / \
        alltoall.cost_hierarchical(B, 8, 8, alltoall.PCIE, alltoall.ETH100)
    assert 1.2 < s4 < 3.0, s4       # paper: 1.66× at 4×8
    assert s4 < s8 < 4.0, (s4, s8)  # paper: 2× at 8×8 — grows with N


def test_cost_model_message_aggregation():
    """The mechanism: G× fewer inter-node messages, G× larger each,
    identical NIC bytes — the win is pure per-message overhead."""
    B, N, G = 16e6, 8, 8
    M = N * G
    # message counts through one NIC
    assert G * (N - 1) == G * G * (N - 1) / G
    # message sizes: B/(G·N) flat → B/N hier (paper: G² aggregation of
    # the per-GPU-pair chunks into per-node bundles)
    assert (B / N) / (B / M) == G
    # NIC bytes identical
    flat_bytes = G * (M - G) / M * B
    hier_bytes = G * (N - 1) / N * B
    assert abs(flat_bytes - hier_bytes) < 1e-6


def test_hierarchical_inner_must_divide_axis(mesh_model8):
    """Bad config fails loudly at trace time (no silent flat fallback,
    no opaque reshape assert inside shard_map)."""
    x = jax.random.normal(RNG, (64, 4, 8))
    with pytest.raises(ValueError, match="a2a_inner"):
        _run(mesh_model8, lambda v: alltoall.all_to_all(
            v, "model", mode="hierarchical", inner=3))(x)
    with pytest.raises(ValueError, match="outer"):
        _run(mesh_model8, lambda v: alltoall.all_to_all(
            v, "model", mode="hierarchical", inner=2, outer=3))(x)


def test_unknown_a2a_mode_rejected():
    """A typo'd mode must raise naming A2A_MODES whatever ``inner`` is:
    with inner<=1 it used to silently run flat, with inner>1 it died on
    a bare ``assert`` stripped under ``python -O``."""
    x = jax.random.normal(RNG, (8, 4, 8))
    for inner in (1, 2):
        with pytest.raises(ValueError, match="'flat', 'hierarchical'"):
            alltoall.all_to_all(x, "model", mode="ring", inner=inner)


def test_bad_a2a_inner_rejected_by_config():
    from repro.core.config import MoEConfig
    with pytest.raises(ValueError, match="a2a_inner"):
        MoEConfig(num_experts=8, a2a_inner=0)
    with pytest.raises(ValueError, match="grouped_ep_bound_factor"):
        MoEConfig(num_experts=8, grouped_ep_bound_factor=0.0)


def test_bad_a2a_inner_rejected_by_moe_layer(mesh_model8):
    """The MoE entry point names the config fields before tracing."""
    from repro.core import moe
    from repro.core.config import MoEConfig
    cfg = MoEConfig(num_experts=8, gate="switch", a2a="hierarchical",
                    a2a_inner=3)
    p = moe.init_moe_params(RNG, cfg, 16, 32, 8, act="swiglu",
                            dtype=jnp.float32)
    x = jax.random.normal(RNG, (8, 4, 16))
    with pytest.raises(ValueError, match="a2a_inner"):
        moe.sharded_moe_apply(mesh_model8, cfg, p, x, num_experts=8,
                              act="swiglu")


# ---------------------------------------------------------------------------
# grouped exchange (dropless EP): counts + bounded segments
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,inner", [("flat", 1), ("hierarchical", 2),
                                        ("hierarchical", 4)])
def test_grouped_a2a_counts_and_tokens_land_source_major(mesh_model8, mode,
                                                         inner):
    """recv chunk s on rank r == send chunk r on rank s, for both the
    token payload and the count matrix, in every a2a mode."""
    M, B, d, E_local = 8, 4, 16, 2
    x = jax.random.normal(RNG, (M * M, B, d))          # per-device (M, B, d)
    counts = jnp.arange(M * M * E_local, dtype=jnp.int32).reshape(
        M * M, E_local)

    def fn(v, c):
        return alltoall.grouped_all_to_all(v, c, "model", mode=mode,
                                           inner=inner)

    recv_x, recv_c = jax.jit(jax.shard_map(
        fn, mesh=mesh_model8, in_specs=(P("model"), P("model")),
        out_specs=(P("model"), P("model")), check_vma=False))(x, counts)
    # global views: sender s's chunk for dest r is x[s*M + r]
    rx = np.asarray(recv_x).reshape(M, M, B, d)        # [rank, src, ...]
    rc = np.asarray(recv_c).reshape(M, M, E_local)
    sx = np.asarray(x).reshape(M, M, B, d)             # [rank, dest, ...]
    sc = np.asarray(counts).reshape(M, M, E_local)
    for r in range(M):
        for s in range(M):
            np.testing.assert_array_equal(rx[r, s], sx[s, r])
            np.testing.assert_array_equal(rc[r, s], sc[s, r])


def test_grouped_a2a_gradient(mesh_model8):
    x = jax.random.normal(RNG, (64, 4, 8))
    counts = jnp.ones((64, 2), jnp.int32)

    def loss(v):
        out, _ = jax.shard_map(
            lambda u, c: alltoall.grouped_all_to_all(
                u, c, "model", mode="hierarchical", inner=4),
            mesh=mesh_model8, in_specs=(P("model"), P("model")),
            out_specs=(P("model"), P("model")), check_vma=False)(v, counts)
        return jnp.sum(out ** 2)

    g = jax.jit(jax.grad(loss))(x)
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x), rtol=1e-6)


# ---------------------------------------------------------------------------
# a2a_inner validation (bugfix: inner < 1 silently ran the flat path,
# disabling the paper's hierarchical win with no signal)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inner", [0, -1])
def test_inner_below_one_raises_naming_the_config_field(inner):
    x = jnp.zeros((8, 4, 8))
    with pytest.raises(ValueError, match="a2a_inner"):
        alltoall.all_to_all(x, "model", mode="hierarchical", inner=inner)
    with pytest.raises(ValueError, match="a2a_inner"):
        alltoall.all_to_all(x, "model", mode="flat", inner=inner)


def test_inner_one_is_the_documented_degenerate_flat_case(mesh_model8):
    x = jax.random.normal(RNG, (64, 4, 16))
    flat = _run(mesh_model8, lambda v: alltoall.flat_all_to_all(v, "model"))
    deg = _run(mesh_model8, lambda v: alltoall.all_to_all(
        v, "model", mode="hierarchical", inner=1))
    np.testing.assert_array_equal(np.asarray(flat(x)), np.asarray(deg(x)))


# ---------------------------------------------------------------------------
# quantized exchange (payload_dtype): wire dtype, scales, round trips
# ---------------------------------------------------------------------------

# |dequant(quantize(x)) - x| <= tol · chunk_amax — grid-step bounds:
# int8 rounds to a 1/127 grid (half-step 0.004); float8_e4m3fn carries
# 3 mantissa bits (rel. step 2^-3, half-step ~6% of the element);
# float8_e5m2 carries 2 (half-step ~12.5%).
QUANT_TOLS = {
    "int8": 0.005,
    "float8_e4m3fn": 0.07,
    "float8_e5m2": 0.15,
}


@pytest.mark.parametrize("qdt", sorted(alltoall.PAYLOAD_QMAX))
def test_quantize_payload_round_trip_within_grid_step(qdt):
    chunk_mag = jnp.array([0.1, 1.0, 10.0, 100.0])    # scale-varied chunks
    x = jax.random.normal(RNG, (4, 16, 32)) * chunk_mag[:, None, None]
    q, s = alltoall.quantize_payload(x, qdt)
    assert q.dtype == jnp.dtype(qdt)
    assert s.shape == (4,) and s.dtype == jnp.float32
    y = np.asarray(alltoall.dequantize_payload(q, s, jnp.float32))
    xf = np.asarray(x, np.float32)
    amax = np.max(np.abs(xf), axis=(1, 2), keepdims=True)
    assert np.all(np.abs(y - xf) <= QUANT_TOLS[qdt] * amax)


def test_quantize_payload_zero_chunk_round_trips_exactly():
    q, s = alltoall.quantize_payload(jnp.zeros((2, 8, 4)), "int8")
    np.testing.assert_array_equal(np.asarray(s), 1.0)
    np.testing.assert_array_equal(
        np.asarray(alltoall.dequantize_payload(q, s, jnp.float32)), 0.0)


def test_unknown_payload_dtype_raises():
    with pytest.raises(ValueError, match="payload"):
        alltoall.quantize_payload(jnp.zeros((2, 4, 4)), "int4")


@pytest.mark.parametrize("mode,inner", [("flat", 1), ("hierarchical", 2)])
@pytest.mark.parametrize("qdt", ["int8", "float8_e4m3fn"])
def test_quantized_exchange_matches_unquantized(mesh_model8, qdt, mode,
                                                inner):
    """Same chunk permutation as grouped_all_to_all; counts cross EXACTLY
    (the scales ride as a bitcast int32 column of the count exchange);
    tokens agree within the per-chunk grid step."""
    M, B, d, E_local = 8, 4, 16, 2
    x = jax.random.normal(RNG, (M * M, B, d))
    counts = jnp.arange(M * M * E_local, dtype=jnp.int32).reshape(
        M * M, E_local)

    def run(f):
        return jax.jit(jax.shard_map(
            f, mesh=mesh_model8, in_specs=(P("model"), P("model")),
            out_specs=(P("model"), P("model")), check_vma=False))(x, counts)

    rx, rc = run(lambda v, c: alltoall.grouped_all_to_all(
        v, c, "model", mode=mode, inner=inner))
    qx, qc = run(lambda v, c: alltoall.quantized_exchange(
        v, c, "model", mode=mode, inner=inner, payload_dtype=qdt))
    np.testing.assert_array_equal(np.asarray(rc), np.asarray(qc))
    assert qx.dtype == x.dtype                 # dequantized on arrival
    rxf = np.asarray(rx, np.float32)
    amax = np.max(np.abs(rxf), axis=(1, 2), keepdims=True)
    assert np.all(np.abs(np.asarray(qx, np.float32) - rxf)
                  <= QUANT_TOLS[qdt] * amax)


def test_quantized_exchange_combine_direction_returns_f32(mesh_model8):
    """counts=None (combine direction): scales go over their own tiny
    flat exchange and the result lands in f32 so the combine reduction
    accumulates at full precision."""
    x = jax.random.normal(RNG, (64, 4, 8), dtype=jnp.bfloat16)

    def fn(v):
        out, rc = alltoall.quantized_exchange(
            v, None, "model", payload_dtype="int8", out_dtype=jnp.float32)
        assert rc is None
        return out

    out = _run(mesh_model8, fn)(x)
    assert out.dtype == jnp.float32
    ref = np.asarray(_run(mesh_model8, lambda v: alltoall.flat_all_to_all(
        v, "model"))(x), np.float32)
    amax = np.max(np.abs(ref), axis=(1, 2), keepdims=True)
    assert np.all(np.abs(np.asarray(out) - ref) <= 0.01 * amax)


def test_quantized_exchange_gradient_is_quantized_involution(mesh_model8):
    """d/dx sum(a2a(x)^2) = 2x for a permutation; the quantized VJP
    sends the cotangent through the SAME low-precision wire, so the
    gradient matches to two grid steps — and never recomputes the
    forward (the residuals carry only the count matrix)."""
    x = jax.random.normal(RNG, (64, 4, 8))
    counts = jnp.ones((64, 2), jnp.int32)

    def loss(v):
        out, _ = jax.shard_map(
            lambda u, c: alltoall.quantized_exchange(
                u, c, "model", mode="hierarchical", inner=4,
                payload_dtype="int8"),
            mesh=mesh_model8, in_specs=(P("model"), P("model")),
            out_specs=(P("model"), P("model")), check_vma=False)(v, counts)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g = np.asarray(jax.jit(jax.grad(loss))(x))
    ref = 2 * np.asarray(x)
    assert np.abs(g - ref).max() <= 0.03 * np.abs(ref).max()
