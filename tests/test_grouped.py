"""Dropless grouped dispatch + sort-once plan state + blocked kernels.

Covers the acceptance properties of the grouped mode: equivalence with
the sort path when capacity is non-binding, zero drops when it is, and
bit-identity of the blocked layout kernels against the jnp oracles
across block sizes including ragged tails.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import analysis
from repro.core import capacity, gating, layout, moe
from repro.core.config import MoEConfig
from repro.kernels import grouped_ffn as gffn
from repro.kernels import ref
from repro.kernels.grouped_ffn import grouped_ffn, grouped_matmul
from repro.kernels.layout_transform import gather_rows, scatter_add_rows

RNG = jax.random.PRNGKey(9)
D = 32


def _params(cfg, E, dtype=jnp.float32):
    return moe.init_moe_params(RNG, cfg, D, 64, E, act="swiglu", dtype=dtype)


# ---------------------------------------------------------------------------
# sort-once plan state
# ---------------------------------------------------------------------------

def test_plan_sort_carries_consistent_sort_state():
    S, E, k = 64, 8, 2
    cfg = MoEConfig(num_experts=E, gate="topk", top_k=k, capacity_factor=1.0)
    g = gating.route(cfg, jax.random.normal(RNG, (S, E)))
    C = capacity.expert_capacity(cfg, S, E)
    plan = layout.plan_sort(g, E, C)
    counts = np.asarray(plan.counts)
    offsets = np.asarray(plan.offsets)
    # counts are the pre-capacity per-expert assignment totals
    expect = np.bincount(np.asarray(g.expert_index).ravel(), minlength=E)
    np.testing.assert_array_equal(counts, expect)
    np.testing.assert_array_equal(offsets, np.concatenate(
        [[0], np.cumsum(counts)]))
    # the permutation really sorts the k-major expert ids, stably
    flat_e = np.asarray(g.expert_index).T.reshape(-1)
    order = np.asarray(plan.sort_order)
    assert (np.diff(flat_e[order]) >= 0).all()
    # inverse map agrees with the token-side slots
    inv = np.asarray(plan.inv)
    slot = np.asarray(plan.slot)
    for s in range(S):
        for j in range(k):
            if slot[s, j] >= 0:
                assert inv[slot[s, j]] == s
    assert (inv[np.setdiff1d(np.arange(E * C),
                             slot[slot >= 0].ravel())] == -1).all()


def test_dispatch_via_inv_equals_scatter():
    S, E = 96, 8
    cfg = MoEConfig(num_experts=E, gate="topk", top_k=2, capacity_factor=1.0)
    x = jax.random.normal(RNG, (S, D))
    g = gating.route(cfg, jax.random.normal(RNG, (S, E)))
    C = capacity.expert_capacity(cfg, S, E)
    plan = layout.plan_sort(g, E, C)
    buf = layout.dispatch_scatter(x, plan, E, C)      # inv-gather path
    fallback = plan._replace(sort_order=None, counts=None,
                             offsets=None, inv=None)
    buf2 = layout.dispatch_scatter(x, fallback, E, C)  # token-scatter path
    np.testing.assert_allclose(np.asarray(buf), np.asarray(buf2),
                               rtol=1e-6, atol=1e-6)


def test_plan_cumsum_counts_match_sort():
    S, E = 64, 8
    cfg = MoEConfig(num_experts=E, gate="topk", top_k=2, capacity_factor=1.0)
    g = gating.route(cfg, jax.random.normal(RNG, (S, E)))
    C = capacity.expert_capacity(cfg, S, E)
    p1 = layout.plan_sort(g, E, C)
    p2 = layout.plan_cumsum(g, E, C)
    np.testing.assert_array_equal(np.asarray(p1.slot), np.asarray(p2.slot))
    np.testing.assert_array_equal(np.asarray(p1.counts),
                                  np.asarray(p2.counts))
    np.testing.assert_array_equal(np.asarray(p1.offsets),
                                  np.asarray(p2.offsets))


# ---------------------------------------------------------------------------
# grouped mode: equivalence + dropless
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gate,kw", [
    ("switch", {}), ("topk", dict(top_k=2)), ("gshard", {})])
def test_grouped_equals_sort_when_capacity_ample(mesh1, gate, kw):
    E = 8
    cfg_s = MoEConfig(num_experts=E, gate=gate, capacity_factor=8.0,
                      dispatch="sort", **kw)
    cfg_g = MoEConfig(num_experts=E, gate=gate, capacity_factor=8.0,
                      dispatch="grouped", **kw)
    p = _params(cfg_s, E)
    x = jax.random.normal(RNG, (4, 16, D))
    ys, auxs, ms = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh1, cfg_s, p, v, num_experts=E, act="swiglu"))(p, x)
    yg, auxg, mg = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh1, cfg_g, p, v, num_experts=E, act="swiglu"))(p, x)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(yg), atol=1e-5)
    np.testing.assert_allclose(float(auxs), float(auxg), rtol=1e-6)
    np.testing.assert_allclose(float(ms["expert_load_max"]),
                               float(mg["expert_load_max"]), rtol=1e-6)


def test_grouped_matches_sort_in_bf16(mesh1):
    """Grouped matmuls accumulate f32 like the sort path's einsum, so
    bf16 params stay within bf16 rounding of the sort path."""
    E = 8
    cfg_s = MoEConfig(num_experts=E, gate="topk", top_k=2,
                      capacity_factor=8.0, dispatch="sort")
    cfg_g = MoEConfig(num_experts=E, gate="topk", top_k=2,
                      capacity_factor=8.0, dispatch="grouped")
    p = _params(cfg_s, E, dtype=jnp.bfloat16)
    x = jax.random.normal(RNG, (4, 16, D), jnp.bfloat16)
    ys, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh1, cfg_s, p, v, num_experts=E, act="swiglu"))(p, x)
    yg, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh1, cfg_g, p, v, num_experts=E, act="swiglu"))(p, x)
    np.testing.assert_allclose(np.asarray(ys, np.float32),
                               np.asarray(yg, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_grouped_is_dropless_where_sort_drops(mesh1):
    """cf=0.25 drops ~3/4 of tokens on the sort path; the grouped path
    computes every token and matches the no-drop reference everywhere."""
    E = 4
    cfg_s = MoEConfig(num_experts=E, gate="switch", capacity_factor=0.25,
                      dispatch="sort")
    cfg_g = MoEConfig(num_experts=E, gate="switch", capacity_factor=0.25,
                      dispatch="grouped")
    cfg_ref = MoEConfig(num_experts=E, gate="switch", capacity_factor=16.0,
                        dispatch="sort")
    p = _params(cfg_s, E)
    x = jax.random.normal(RNG, (8, 32, D))
    ys, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh1, cfg_s, p, v, num_experts=E, act="swiglu"))(p, x)
    yg, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh1, cfg_g, p, v, num_experts=E, act="swiglu"))(p, x)
    yr, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh1, cfg_ref, p, v, num_experts=E, act="swiglu"))(p, x)
    dropped = np.isclose(np.asarray(ys).reshape(-1, D), 0).all(axis=1)
    assert dropped.sum() > 64               # capacity really binds
    # grouped == unconstrained reference on every token, incl. dropped ones
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yr), atol=1e-5)
    live = np.abs(np.asarray(yg).reshape(-1, D)).sum(axis=1)
    assert (live[dropped] > 0).all()        # zero tokens dropped


def test_grouped_pallas_matches_ragged(mesh1):
    E = 8
    res = {}
    for pall in (False, True):
        cfg = MoEConfig(num_experts=E, gate="switch", capacity_factor=2.0,
                        dispatch="grouped", use_pallas_gate=pall)
        p = _params(cfg, E)
        x = jax.random.normal(RNG, (2, 16, D))

        def loss(p, v):
            y, aux, _ = moe.sharded_moe_apply(mesh1, cfg, p, v,
                                              num_experts=E, act="swiglu")
            return jnp.sum(y ** 2) + aux

        l, g = jax.jit(jax.value_and_grad(loss))(p, x)
        res[pall] = (float(l), float(jnp.linalg.norm(g["gate_w"])),
                     float(jnp.linalg.norm(g["w_up"])))
    np.testing.assert_allclose(res[False], res[True], rtol=1e-4)


# ---------------------------------------------------------------------------
# grouped expert parallelism: the grouped AllToAll (no more sort fallback)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("a2a,inner", [("flat", 1), ("hierarchical", 2)])
def test_grouped_ep_matches_sort_and_dense(mesh8, a2a, inner):
    """Ample capacity (no drops anywhere): grouped-EP ≡ sort ≡ dense on
    the 2×4 mesh, with both the flat and the hierarchical exchange."""
    E = 8
    x = jax.random.normal(RNG, (4, 16, D))
    ys = {}
    for mode in ("grouped", "sort", "dense"):
        cfg = MoEConfig(num_experts=E, gate="topk", top_k=2,
                        capacity_factor=8.0, dispatch=mode,
                        a2a=a2a, a2a_inner=inner)
        p = _params(cfg, E)
        ys[mode], _, _ = jax.jit(lambda p, v, cfg=cfg: moe.sharded_moe_apply(
            mesh8, cfg, p, v, num_experts=E, act="swiglu"))(p, x)
    np.testing.assert_allclose(np.asarray(ys["grouped"]),
                               np.asarray(ys["sort"]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ys["grouped"]),
                               np.asarray(ys["dense"]), rtol=1e-4, atol=1e-5)


def test_grouped_ep_matches_single_device(mesh1, mesh_ep4):
    """4-way grouped EP reproduces the single-device grouped numerics."""
    E = 8
    cfg = MoEConfig(num_experts=E, gate="topk", top_k=2, capacity_factor=8.0,
                    dispatch="grouped")
    p = _params(cfg, E)
    x = jax.random.normal(RNG, (4, 16, D))
    y1, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh1, cfg, p, v, num_experts=E, act="swiglu"))(p, x)
    y4, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh_ep4, cfg, p, v, num_experts=E, act="swiglu"))(p, x)
    # (aux losses are per-shard means and legitimately differ by mesh —
    # same as the sort path; only the token outputs must agree)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4),
                               rtol=1e-4, atol=1e-5)


def test_grouped_ep_hierarchical_equals_flat(mesh_ep4):
    """The paper's two-stage exchange composes with dropless dispatch:
    identical layer output either way (inner=2 × outer=2)."""
    E = 8
    x = jax.random.normal(RNG, (4, 16, D))
    cfgf = MoEConfig(num_experts=E, gate="switch", capacity_factor=4.0,
                     dispatch="grouped")
    cfgh = MoEConfig(num_experts=E, gate="switch", capacity_factor=4.0,
                     dispatch="grouped", a2a="hierarchical", a2a_inner=2)
    p = _params(cfgf, E)
    yf, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh_ep4, cfgf, p, v, num_experts=E, act="swiglu"))(p, x)
    yh, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh_ep4, cfgh, p, v, num_experts=E, act="swiglu"))(p, x)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yh),
                               rtol=1e-5, atol=1e-6)


def test_grouped_ep_is_dropless_where_sort_drops(mesh_ep4):
    """cf=0.25 starves the sort path; grouped-EP ignores capacity_factor
    and matches the unconstrained reference on every token."""
    E = 8
    cfg_g = MoEConfig(num_experts=E, gate="switch", capacity_factor=0.25,
                      dispatch="grouped")
    cfg_ref = MoEConfig(num_experts=E, gate="switch", capacity_factor=16.0,
                        dispatch="sort")
    p = _params(cfg_g, E)
    x = jax.random.normal(RNG, (8, 32, D))
    yg, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh_ep4, cfg_g, p, v, num_experts=E, act="swiglu"))(p, x)
    yr, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh_ep4, cfg_ref, p, v, num_experts=E, act="swiglu"))(p, x)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yr),
                               rtol=1e-4, atol=1e-5)


def test_grouped_ep_token_padding_path(mesh8):
    """Virtual-expert rows (3 tokens on 8 devices) never enter the
    exchange; output is finite and matches the sort path's."""
    E = 8
    cfg_g = MoEConfig(num_experts=E, gate="switch", capacity_factor=8.0,
                      dispatch="grouped")
    cfg_s = MoEConfig(num_experts=E, gate="switch", capacity_factor=8.0,
                      dispatch="sort")
    p = _params(cfg_g, E)
    x = jax.random.normal(RNG, (3, 1, D))
    yg, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh8, cfg_g, p, v, num_experts=E, act="swiglu"))(p, x)
    ys, _, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh8, cfg_s, p, v, num_experts=E, act="swiglu"))(p, x)
    assert yg.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(yg)))
    np.testing.assert_allclose(np.asarray(yg), np.asarray(ys),
                               rtol=1e-4, atol=1e-5)


def test_grouped_ep_gradients_flow(mesh8):
    cfg = MoEConfig(num_experts=8, gate="switch", capacity_factor=4.0,
                    dispatch="grouped")
    p = _params(cfg, 8)
    x = jax.random.normal(RNG, (4, 16, D))

    def loss(p, v):
        y, aux, _ = moe.sharded_moe_apply(mesh8, cfg, p, v,
                                          num_experts=8, act="swiglu")
        return jnp.sum(y ** 2) + aux

    g = jax.jit(jax.grad(loss))(p, x)
    for k, v in g.items():
        assert bool(jnp.all(jnp.isfinite(v))), k
        assert float(jnp.linalg.norm(v)) > 0, k


def test_grouped_ep_pallas_matches_jnp(mesh_ep4):
    """The Pallas gather/grouped-matmul kernels drive the EP exchange
    end to end and agree with the jnp/ragged path, value and grad."""
    E = 8
    res = {}
    for pall in (False, True):
        cfg = MoEConfig(num_experts=E, gate="switch", capacity_factor=2.0,
                        dispatch="grouped", use_pallas_gate=pall)
        p = _params(cfg, E)
        x = jax.random.normal(RNG, (2, 16, D))

        def loss(p, v, cfg=cfg):
            y, aux, _ = moe.sharded_moe_apply(mesh_ep4, cfg, p, v,
                                              num_experts=E, act="swiglu")
            return jnp.sum(y ** 2) + aux

        l, g = jax.jit(jax.value_and_grad(loss))(p, x)
        res[pall] = (float(l), float(jnp.linalg.norm(g["gate_w"])),
                     float(jnp.linalg.norm(g["w_up"])))
    np.testing.assert_allclose(res[False], res[True], rtol=1e-4)


@pytest.mark.parametrize("a2a,inner", [("flat", 1), ("hierarchical", 2)])
def test_grouped_ep_bound_drops_deterministically(mesh_ep4, a2a, inner):
    """``grouped_ep_bound_factor < 1`` drops EXACTLY the lowest-priority
    rows of each over-subscribed (source rank → dest rank) segment — the
    tail of the expert-sorted segment, so within each expert the kept
    rows are the stable sort's highest-priority prefix (slot-major:
    1st choices before 2nd choices, earlier tokens first) — identically
    across reruns and across both a2a modes; and the aux-loss load
    metrics still count the dropped assignments (they derive from the
    ROUTING counts, not the post-drop exchange counts)."""
    E, M = 8, 4
    cfg = MoEConfig(num_experts=E, gate="topk", top_k=2, capacity_factor=8.0,
                    dispatch="grouped", grouped_ep_bound_factor=0.5,
                    a2a=a2a, a2a_inner=inner)
    p = _params(cfg, E)
    x = jax.random.normal(RNG, (8, 16, D))        # 128 tokens, 32 per rank

    def fn(p, v):
        return moe.sharded_moe_apply(mesh_ep4, cfg, p, v,
                                     num_experts=E, act="swiglu")

    y1, _, m1 = jax.jit(fn)(p, x)
    y2, _, m2 = jax.jit(fn)(p, x)                 # fresh jit, same result
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert float(m1["expert_load_max"]) == float(m2["expert_load_max"])

    toks = np.asarray(x.reshape(-1, D))
    S_l = toks.shape[0] // M
    B = capacity.grouped_segment_bound(cfg, S_l, M)
    got = np.asarray(y1, np.float32).reshape(-1, D)
    load_max = []
    for m in range(M):
        xs = jnp.asarray(toks[m * S_l:(m + 1) * S_l])
        g = gating.route(cfg, gating.router_logits(cfg, xs, p["gate_w"]))
        gplan = layout.plan_grouped(g, E, drop_bucket=True)
        ep = layout.plan_grouped_ep(gplan, E, M, B)
        back = np.asarray(ep.back_map)
        sc = np.asarray(ep.send_counts).reshape(-1)   # (E,) routing order
        counts = np.asarray(gplan.counts)
        offs = np.asarray(gplan.offsets)
        # binding bound: something actually drops on this shard
        assert sc.sum() < counts.sum()
        # the kept rows of every expert segment are its PREFIX — the
        # highest-priority assignments survive, the tail drops
        for e in range(E):
            assert (back[offs[e]:offs[e] + sc[e]] >= 0).all()
            assert (back[offs[e] + sc[e]:offs[e + 1]] == -1).all()
        # expected per-token output: only surviving assignments contribute
        K = g.expert_index.shape[1]
        surv = np.zeros((S_l, K), bool)
        order = np.asarray(gplan.sort_order)
        token = np.asarray(gplan.token)
        for r in range(offs[E]):
            if back[r] >= 0:
                surv[token[r], order[r] // S_l] = True
        ye = moe.expert_ffn(
            {k: v for k, v in p.items() if k != "gate_w"},
            jnp.broadcast_to(xs, (E, S_l, D)), "swiglu")      # (E, S_l, d)
        w = np.asarray(g.combine_weights)
        idx = np.asarray(g.expert_index)
        expect = np.zeros((S_l, D), np.float32)
        for s in range(S_l):
            for k in range(K):
                if surv[s, k]:
                    expect[s] += w[s, k] * np.asarray(ye[idx[s, k], s],
                                                      np.float32)
        np.testing.assert_allclose(got[m * S_l:(m + 1) * S_l], expect,
                                   rtol=1e-4, atol=1e-5, err_msg=f"rank {m}")
        load_max.append(counts.max() / counts.sum())
    # load metrics count the dropped assignments: the pmean'd stat is the
    # shard mean of ROUTING-count maxima, not of the clipped send counts
    np.testing.assert_allclose(float(m1["expert_load_max"]),
                               np.mean(load_max), rtol=1e-5)


def test_grouped_ep_tight_bound_drops_gracefully(mesh_ep4):
    """A binding segment bound behaves like sort-path capacity: finite
    output, dropped rows fall back to the residual (zero layer output)."""
    E = 8
    cfg = MoEConfig(num_experts=E, gate="switch", capacity_factor=4.0,
                    dispatch="grouped", grouped_ep_bound_factor=1.0)
    p = _params(cfg, E)
    x = jax.random.normal(RNG, (8, 16, D))
    y, aux, _ = jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh_ep4, cfg, p, v, num_experts=E, act="swiglu"))(p, x)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert bool(jnp.isfinite(aux))


# ---------------------------------------------------------------------------
# grouped-EP plan state (send/receive maps, no collectives)
# ---------------------------------------------------------------------------

def test_grouped_ep_plan_maps_are_consistent():
    S, E, K, M = 64, 8, 2, 4
    cfg = MoEConfig(num_experts=E, gate="topk", top_k=K)
    g = gating.route(cfg, jax.random.normal(RNG, (S, E)))
    gplan = layout.plan_grouped(g, E, drop_bucket=True)
    B = S * K
    ep = layout.plan_grouped_ep(gplan, E, M, B)
    pack = np.asarray(ep.pack_map)
    back = np.asarray(ep.back_map)
    token = np.asarray(gplan.token)
    offsets = np.asarray(gplan.offsets)
    # every non-virtual sorted row has a slot, and the slot's pack entry
    # names the same source token
    for r in range(offsets[E]):
        assert back[r] >= 0
        assert pack[back[r]] == token[r]
    # virtual-bucket tail rows get no slot
    assert (back[offsets[E]:] == -1).all()
    # send_counts match the routing counts at the dropless bound
    E_local = E // M
    np.testing.assert_array_equal(
        np.asarray(ep.send_counts).reshape(-1), np.asarray(gplan.counts))
    # a binding bound truncates segment tails, never exceeds B
    ep2 = layout.plan_grouped_ep(gplan, E, M, 8)
    sc2 = np.asarray(ep2.send_counts)
    assert (sc2.sum(axis=1) <= 8).all()
    assert (sc2 <= np.asarray(gplan.counts).reshape(M, E_local)).all()


def test_grouped_ep_receive_maps_invert():
    M, E_local, B = 4, 2, 16
    rng = np.random.RandomState(0)
    counts = rng.randint(0, 6, (M, E_local)).astype(np.int32)
    ffn_src, dst_map, sizes = layout.grouped_ep_receive_maps(
        jnp.asarray(counts), B)
    ffn_src, dst_map = np.asarray(ffn_src), np.asarray(dst_map)
    np.testing.assert_array_equal(np.asarray(sizes), counts.sum(axis=0))
    # dst/src are mutual inverses on the live slots
    for i, dsti in enumerate(dst_map):
        if dsti >= 0:
            assert ffn_src[dsti] == i
    n = counts.sum()
    assert (np.sort(dst_map[dst_map >= 0]) == np.arange(n)).all()
    assert (ffn_src[n:] == -1).all()
    # FFN rows are expert-major: walking dst for chunk m visits local
    # expert segments in order
    e_of_ffn = np.searchsorted(np.cumsum(counts.sum(axis=0)),
                               np.arange(n), side="right")
    for m in range(M):
        off = 0
        for e in range(E_local):
            for j in range(counts[m, e]):
                assert e_of_ffn[dst_map[m * B + off + j]] == e
            off += counts[m, e]


# ---------------------------------------------------------------------------
# blocked kernels: bit-identity vs jnp across block sizes + ragged tails
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,M,d,bm", [
    (64, 64, 128, 16),     # exact multiple
    (100, 37, 64, 8),      # ragged tail (37 % 8 != 0)
    (8, 5, 16, 128),       # M < block_m
    (3, 200, 8, 64),       # tiny source, many rows
    (33, 130, 8, 128),     # one full + one ragged block
    (40, 37, 256, 8),      # d = 2·128: rows move as (d/128, 128) tiles
])
def test_blocked_gather_bit_identical(N, M, d, bm):
    key = jax.random.PRNGKey(N * M)
    src = jax.random.normal(key, (N, d))
    idx = jax.random.randint(key, (M,), -2, N)
    out = gather_rows(src, idx, True, bm)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.ref_gather_rows(src, idx)))


@pytest.mark.parametrize("N,M,d,bm", [
    (64, 64, 32, 16), (16, 37, 8, 8), (8, 5, 16, 128),
    (16, 37, 256, 8)])      # d = 2·128: rows move as (d/128, 128) tiles
def test_blocked_scatter_add_matches_jnp(N, M, d, bm):
    key = jax.random.PRNGKey(M)
    g = jax.random.normal(key, (M, d))
    idx = jax.random.randint(key, (M,), -2, N)       # dups + drops
    out = scatter_add_rows(g, idx, N, interpret=True, block_m=bm)
    expect = np.zeros((N, d), np.float32)
    for j, i in enumerate(np.asarray(idx)):
        if i >= 0:
            expect[i] += np.asarray(g)[j]
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6, atol=1e-6)


def test_blocked_gather_vjp_uses_blocked_scatter():
    src = jax.random.normal(RNG, (8, 16))
    idx = jnp.array([0, 0, 3, -1, 7])
    for bm in (2, 128):
        g = jax.grad(lambda s: jnp.sum(gather_rows(s, idx, True, bm) ** 2))(src)
        out = np.asarray(ref.ref_gather_rows(src, idx))
        expect = np.zeros((8, 16), np.float32)
        for j, i in enumerate([0, 0, 3, -1, 7]):
            if i >= 0:
                expect[i] += 2 * out[j]
        np.testing.assert_allclose(np.asarray(g), expect, rtol=1e-5)


# ---------------------------------------------------------------------------
# grouped matmul kernel vs lax.ragged_dot
# ---------------------------------------------------------------------------

def _small_ints(key, shape):
    """Integers in [-4, 4] as f32: every product and partial sum of these
    contractions is exact, so the kernels and ragged_dot agree whatever
    their summation order, even at K = 1024."""
    return jax.random.randint(key, shape, -4, 5).astype(jnp.float32)


@pytest.fixture
def nan_outputs(monkeypatch):
    """Every grouped kernel's output starts as NaN, aliased in through
    ``input_output_aliases``: a block the kernel never writes (a tail row
    block, an empty expert's gradient) then fails the comparison instead
    of reading the interpreter's zeros."""
    def pallas_call(kernel, *, grid_spec, out_shape, **kw):
        gs = grid_spec
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=gs.num_scalar_prefetch, grid=gs.grid,
            in_specs=[*gs.in_specs, gs.out_specs], out_specs=gs.out_specs)

        def call(*args):
            fill = jnp.full(out_shape.shape, jnp.nan, out_shape.dtype)
            return pl.pallas_call(
                lambda *refs: kernel(*refs[:-2], refs[-1]), grid_spec=spec,
                out_shape=out_shape, input_output_aliases={len(args): 0},
                **kw)(*args, fill)
        return call

    monkeypatch.setattr(gffn, "pl", types.SimpleNamespace(
        **{**vars(pl), "pallas_call": pallas_call}))
    impls = (gffn._grouped_matmul_impl, gffn._grouped_drhs_impl)
    for f in impls:
        f.clear_cache()
    yield
    for f in impls:
        f.clear_cache()


@pytest.mark.parametrize("M,bm,sizes", [
    (64, 16, (0, 20, 30, 14)),        # empty first expert
    (64, 16, (20, 0, 30, 14)),        # empty middle expert
    (64, 16, (20, 30, 14, 0)),        # empty last expert
    (64, 16, (0, 64, 0, 0)),          # all rows in one expert
    (100, 16, (7, 13, 9, 21, 50)),    # M not a multiple of block_m
    (40, 128, (10, 0, 25, 5)),        # M < block_m
    (64, 16, (5, 20, 0, 9)),          # tail rows past offsets[-1]
    (64, 16, (0, 0, 0, 0)),           # every row in the tail
])
@pytest.mark.parametrize("visit_empty", [False, True])
def test_visit_list_covers_each_overlap_once_in_row_order(M, bm, sizes,
                                                          visit_empty):
    E = len(sizes)
    bm_, nb = gffn._row_blocks(M, bm)
    offs = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    grp, blk, live = (np.asarray(a) for a in gffn.visit_list(
        jnp.asarray(offs), nb, bm_, visit_empty=visit_empty))
    V, live = nb + E - 1, int(live[0])
    assert grp.shape == blk.shape == (V,) and live <= V
    expect = []
    for e in range(E):
        lo, hi = offs[e], offs[e + 1]
        if hi > lo:
            expect += [(e, b) for b in range(nb)
                       if max(lo, b * bm_) < min(hi, (b + 1) * bm_)]
        elif visit_empty:
            expect.append((e, min(lo // bm_, nb - 1)))
    # every overlapping pair once, and in row order
    assert list(zip(grp[:live].tolist(), blk[:live].tolist())) == expect
    assert (np.diff(blk) >= 0).all()
    # padding repeats the last live visit
    if live:
        assert (grp[live:] == grp[live - 1]).all()
        assert (blk[live:] == blk[live - 1]).all()
    # the forward's list also visits the tail, so every row block is zeroed
    fwd = [np.asarray(a) for a in gffn._fwd_visits(jnp.asarray(offs), nb,
                                                    bm_)]
    assert set(fwd[1][:int(fwd[2][0])].tolist()) == set(range(nb))
    assert int(fwd[2][0]) <= V


@pytest.mark.parametrize("M,K,N,groups,bm,tail", [
    (64, 16, 24, 4, 16, 0),
    (100, 8, 8, 3, 128, 7),     # virtual-bucket tail rows → zeros
    (37, 32, 16, 5, 8, 3),      # ragged blocks + tail
    (64, 1024, 1536, 4, 16, 5),  # 2 x 3 lane tiles of 512 (K != N)
    # row block 3 lies wholly in the tail
    pytest.param(64, 16, 24, (0, 20, 24, 0), 16, 20, id="empty_first_last"),
    pytest.param(64, 16, 24, (0, 64, 0), 16, 0, id="one_expert_all_rows"),
    pytest.param(64, 16, 24, (7, 13, 9, 21, 11, 3), 16, 0,
                 id="boundaries_inside_blocks"),
    pytest.param(520, 128, 256, 5, 256, 9, id="block_m_over_lane_tile"),
])
def test_grouped_matmul_matches_ragged_dot(M, K, N, groups, bm, tail,
                                           nan_outputs):
    """``groups``: the number of experts, cut at random, or their sizes.
    Outputs start as NaN (``nan_outputs``), so every block is written."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(M), 2)
    total = M - tail
    if isinstance(groups, int):
        cuts = np.sort(np.random.RandomState(0).randint(0, total + 1,
                                                        groups - 1))
        groups = np.diff(np.concatenate([[0], cuts, [total]]))
    assert sum(groups) == total
    E = len(groups)
    sizes = jnp.array(groups, jnp.int32)
    lhs = _small_ints(k1, (M, K))
    rhs = _small_ints(k2, (E, K, N))
    out = grouped_matmul(lhs, rhs, sizes, True, bm)
    expect = jax.lax.ragged_dot(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    if tail:
        assert np.allclose(np.asarray(out)[total:], 0.0)
    g1 = jax.grad(lambda l, r: jnp.sum(
        grouped_matmul(l, r, sizes, True, bm) ** 2), (0, 1))(lhs, rhs)
    g2 = jax.grad(lambda l, r: jnp.sum(
        jax.lax.ragged_dot(l, r, sizes) ** 2), (0, 1))(lhs, rhs)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_grouped_grids_walk_visit_lists():
    """Forward, dlhs and drhs: each kernel grid's row axis walks a visit
    list of nb + E - 1 steps, not one step per (expert, row block)."""
    M, K, N, E, bm = 100, 8, 16, 4, 16
    lhs = jax.random.normal(RNG, (M, K))
    rhs = jax.random.normal(RNG, (E, K, N))
    sizes = jnp.array([30, 0, 41, 22], jnp.int32)
    g = analysis.trace_graph(
        jax.grad(lambda l, r: jnp.sum(grouped_matmul(l, r, sizes, True,
                                                     bm) ** 2), (0, 1)),
        lhs, rhs)
    grids = sorted(s.eqn.params["grid_mapping"].grid
                   for s in g.find("pallas_call"))
    nb = -(-M // bm)
    nb_drhs = -(-M // gffn._drhs_tiles(M, K, N, bm)[0])
    assert grids == sorted([(1, nb + E - 1), (1, nb + E - 1),
                            (1, 1, nb_drhs + E - 1)]), grids
    assert all(grid[-1] < E * nb for grid in grids)


def test_grouped_ffn_paths_agree():
    E, d, f = 4, 16, 32
    key = jax.random.PRNGKey(2)
    params = {
        "w_up": jax.random.normal(key, (E, d, f)),
        "w_gate": jax.random.normal(key, (E, d, f)),
        "w_out": jax.random.normal(key, (E, f, d)),
    }
    xs = jax.random.normal(key, (64, d))
    sizes = jnp.array([20, 10, 4, 30], jnp.int32)
    y1 = grouped_ffn(params, xs, sizes, "swiglu", use_pallas=False)
    y2 = grouped_ffn(params, xs, sizes, "swiglu", use_pallas=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Pallas backward (dlhs / drhs kernels) vs the ragged_dot VJP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,rtol,atol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 3e-2, 3e-2)])
@pytest.mark.parametrize("bm", [16, 128])
@pytest.mark.parametrize("M,K,N,group_sizes", [
    (96, 16, 24, (30, 20, 0, 25, 15)),
    # K = 1024, N = 1536: dlhs over 2 and drhs over 2 x 3 lane tiles of 512
    (64, 1024, 1536, (20, 0, 26, 12)),
    (64, 16, 24, (0, 20, 20, 0)),     # at bm 16, block 3 is all tail
    (64, 16, 24, (0, 0, 58, 0)),
    (64, 16, 24, (7, 13, 9, 21, 11)),
], ids=["narrow", "lane_tiled", "empty_first_last", "one_expert",
        "boundaries_inside_blocks"])
def test_grouped_bwd_matches_ragged_vjp(M, K, N, group_sizes, dtype, rtol,
                                        atol, bm, nan_outputs):
    """Explicit-cotangent VJP equality, with drop-bucket tail rows past
    offsets[-1] and EMPTY expert segments (first, middle, last) or every
    expert boundary inside a row block.  Outputs start as NaN
    (``nan_outputs``), so a block no visit writes fails."""
    E, total = len(group_sizes), sum(group_sizes)
    empty = [e for e, n in enumerate(group_sizes) if n == 0]
    lhs = _small_ints(RNG, (M, K)).astype(dtype)
    rhs = _small_ints(jax.random.PRNGKey(1), (E, K, N)).astype(dtype)
    sizes = jnp.array(group_sizes, jnp.int32)              # Σ < M
    g = _small_ints(jax.random.PRNGKey(2), (M, N)).astype(dtype)

    _, vjp_p = jax.vjp(lambda l, r: grouped_matmul(l, r, sizes, True, bm),
                       lhs, rhs)
    _, vjp_r = jax.vjp(lambda l, r: jax.lax.ragged_dot(l, r, sizes),
                       lhs, rhs)
    (dl_p, dr_p), (dl_r, dr_r) = vjp_p(g), vjp_r(g)
    np.testing.assert_allclose(np.asarray(dl_p, np.float32),
                               np.asarray(dl_r, np.float32),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(dr_p, np.float32),
                               np.asarray(dr_r, np.float32),
                               rtol=rtol, atol=atol)
    assert dl_p.dtype == lhs.dtype and dr_p.dtype == rhs.dtype
    # tail rows produce zero output, so their lhs gradient is zero
    assert np.allclose(np.asarray(dl_p, np.float32)[total:], 0.0)
    # an empty expert's weight gradient is exactly zero
    assert (np.asarray(dr_p, np.float32)[empty] == 0.0).all()


def test_grouped_bwd_is_pallas_not_ragged_recompute():
    """The backward must run the dlhs/drhs kernels off the residuals —
    no ragged_dot equation (whose jax.vjp re-ran the whole forward)
    anywhere in the gradient graph, including custom_vjp sub-jaxprs."""
    lhs = jax.random.normal(RNG, (32, 8))
    rhs = jax.random.normal(RNG, (4, 8, 8))
    sizes = jnp.array([10, 6, 0, 16], jnp.int32)
    g = analysis.trace_graph(
        jax.grad(lambda l: jnp.sum(grouped_matmul(l, rhs, sizes, True,
                                                  16) ** 2)),
        lhs, context={"direction": "grad", "expect_no_ragged": True})
    assert analysis.run_rule("no-recompute-backward", g) == []
    # teeth: the raw lax.ragged_dot VJP *does* trip the same rule
    bad = analysis.trace_graph(
        jax.grad(lambda l: jnp.sum(jax.lax.ragged_dot(l, rhs, sizes) ** 2)),
        lhs, context={"direction": "grad", "expect_no_ragged": True})
    assert any(f.rule == "no-recompute-backward"
               for f in analysis.run_rule("no-recompute-backward", bad))


def test_grouped_ffn_swiglu_grads_pallas_matches_ragged():
    E, d, f = 4, 16, 32
    key = jax.random.PRNGKey(2)
    params = {
        "w_up": jax.random.normal(key, (E, d, f)),
        "w_gate": jax.random.normal(key, (E, d, f)),
        "w_out": jax.random.normal(key, (E, f, d)),
    }
    xs = jax.random.normal(key, (64, d))
    sizes = jnp.array([20, 10, 4, 28], jnp.int32)          # 2-row tail

    def loss(p, xs, use_pallas):
        return jnp.sum(grouped_ffn(p, xs, sizes, "swiglu",
                                   use_pallas=use_pallas, block_m=16) ** 2)

    gp, gxp = jax.grad(loss, (0, 1))(params, xs, True)
    gr, gxr = jax.grad(loss, (0, 1))(params, xs, False)
    np.testing.assert_allclose(np.asarray(gxp), np.asarray(gxr),
                               rtol=1e-4, atol=1e-4)
    for k in params:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gr[k]),
                                   rtol=1e-4, atol=1e-4, err_msg=k)


def test_grouped_ep_pallas_grad_smoke(mesh_ep4):
    """jax.grad through the full grouped-EP layer on the Pallas kernel
    path (fwd + new bwd): finite, nonzero expert-weight gradients."""
    E = 8
    cfg = MoEConfig(num_experts=E, gate="switch", capacity_factor=2.0,
                    dispatch="grouped", use_pallas_gate=True)
    p = _params(cfg, E)
    x = jax.random.normal(RNG, (2, 16, D))

    def loss(p, v):
        y, aux, _ = moe.sharded_moe_apply(mesh_ep4, cfg, p, v,
                                          num_experts=E, act="swiglu")
        return jnp.sum(y ** 2) + aux

    g = jax.jit(jax.grad(loss))(p, x)
    for k, v in g.items():
        assert bool(jnp.all(jnp.isfinite(v))), k
        assert float(jnp.linalg.norm(v)) > 0, k


def test_grouped_block_m_threads_through_layer(mesh1):
    """cfg.grouped_block_m reaches the kernels; a non-default block size
    reproduces the default's output and gradients."""
    E = 8
    x = jax.random.normal(RNG, (2, 16, D))
    res = {}
    for bm in (None, 16):
        cfg = MoEConfig(num_experts=E, gate="switch", capacity_factor=2.0,
                        dispatch="grouped", use_pallas_gate=True,
                        grouped_block_m=bm)
        p = _params(cfg, E)

        def loss(p, v, cfg=cfg):
            y, aux, _ = moe.sharded_moe_apply(mesh1, cfg, p, v,
                                              num_experts=E, act="swiglu")
            return jnp.sum(y ** 2) + aux

        l, g = jax.jit(jax.value_and_grad(loss))(p, x)
        res[bm] = (float(l), float(jnp.linalg.norm(g["w_up"])))
    np.testing.assert_allclose(res[None], res[16], rtol=1e-5)


# ---------------------------------------------------------------------------
# quantized exchange wire (PR 10): int8 / fp8 payloads, fwd + grad
# ---------------------------------------------------------------------------

# Normwise relative-error budgets vs the unquantized grouped run (f32
# compute).  Measured on these shapes: int8 outputs land near 1.2%
# relative and e4m3 near 3.4%; gradients flow through the quantized
# backward (the cotangent takes the same wire), which roughly doubles
# the relative spread.  Budgets leave ~3x headroom over the medians.
QWIRE_TOLS = {"int8": (5e-2, 1e-1),
              "float8_e4m3fn": (1.5e-1, 3e-1)}


@pytest.mark.parametrize("qdt", sorted(QWIRE_TOLS))
def test_grouped_ep_quantized_payload_fwd_and_grad(mesh_ep4, qdt):
    """The low-precision exchange wire reproduces the unquantized
    grouped-EP layer — value AND parameter gradients — within the
    documented per-dtype budget, with every gradient finite/nonzero."""
    E = 8
    x = jax.random.normal(RNG, (4, 16, D))
    out_tol, grad_tol = QWIRE_TOLS[qdt]
    runs = {}
    for pd in (None, qdt):
        cfg = MoEConfig(num_experts=E, gate="switch", capacity_factor=4.0,
                        dispatch="grouped", payload_dtype=pd)
        p = _params(cfg, E)

        def loss(p, v, cfg=cfg):
            y, aux, _ = moe.sharded_moe_apply(mesh_ep4, cfg, p, v,
                                              num_experts=E, act="swiglu")
            return jnp.sum(y ** 2) + aux, y

        (l, y), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(p, x)
        runs[pd] = (float(l), np.asarray(y, np.float32),
                    {k: np.asarray(v, np.float32) for k, v in g.items()})

    l0, y0, g0 = runs[None]
    lq, yq, gq = runs[qdt]
    assert abs(lq - l0) / abs(l0) < out_tol
    assert np.linalg.norm(yq - y0) / np.linalg.norm(y0) < out_tol
    for k in g0:
        assert np.all(np.isfinite(gq[k])), k
        assert np.linalg.norm(gq[k]) > 0, k
        err = np.linalg.norm(gq[k] - g0[k]) / np.linalg.norm(g0[k])
        assert err < grad_tol, (qdt, k, err)
