"""Grouped (dropless) dispatch × expert tensor parallelism.

The composition the TP fallback used to forfeit: ``dispatch="grouped"``
with ``expert_tp_axis`` set must run the ragged/grouped matmuls over
f-sliced expert weights — NOT silently rewrite itself to the
capacity-padded sort path.  Covers the full matrix: grouped+TP ≡
sort+TP ≡ dense ≡ no-TP (fwd + grad, f32/bf16), grouped+TP × grouped-EP
on the (data=2, model=2) mesh, both a2a modes, the Pallas kernel path,
and a jaxpr witness that the grouped primitives actually execute under
TP.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.core import moe
from repro.core.config import MoEConfig

RNG = jax.random.PRNGKey(7)
D = 32
E = 8


def _params(cfg, dtype=jnp.float32):
    return moe.init_moe_params(RNG, cfg, D, 64, cfg.num_experts,
                               act="swiglu", dtype=dtype)


def _apply(mesh, cfg, params, x, tp=None):
    return jax.jit(lambda p, v: moe.sharded_moe_apply(
        mesh, cfg, p, v, num_experts=cfg.num_experts, act="swiglu",
        expert_tp_axis=tp))(params, x)


def _cfg(dispatch, **kw):
    kw.setdefault("gate", "topk")
    kw.setdefault("top_k", 2)
    kw.setdefault("capacity_factor", 8.0)
    return MoEConfig(num_experts=E, dispatch=dispatch, **kw)


# ---------------------------------------------------------------------------
# the fallback is gone: grouped primitives execute under TP
# ---------------------------------------------------------------------------

def test_grouped_tp_runs_grouped_path_not_sort(mesh8):
    """The traced grouped+TP graph must contain the ragged grouped
    matmul equation (the dropless compute) — the old fallback lowered to
    the sort path's dense einsum and no ragged_dot appeared anywhere."""
    cfg = _cfg("grouped")
    p = _params(cfg)
    x = jax.random.normal(RNG, (8, 4, D))
    g = analysis.trace_graph(
        lambda p_, v: moe.sharded_moe_apply(mesh8, cfg, p_, v, num_experts=E,
                                            act="swiglu",
                                            expert_tp_axis="data"), p, x)
    assert g.count(analysis.RAGGED_DOT_PRIMITIVES) > 0
    # and the TP collectives surround it (gather the segments, reduce
    # the f-contraction) — the capacity-padded (E·C) buffer path would
    # show neither with these shapes
    assert g.count("all_gather") + g.count("all_gather_invariant") > 0
    assert g.count("psum_scatter") + g.count("reduce_scatter") > 0
    # every primitive sits outside scan/while bodies (statically
    # unrolled pipeline), so the loop-collective rule stays quiet
    assert analysis.run_rule("collective-in-loop", g) == []


# ---------------------------------------------------------------------------
# equivalence: grouped+TP ≡ sort+TP ≡ dense ≡ grouped no-TP
# ---------------------------------------------------------------------------

def test_grouped_tp_matches_sort_tp_and_dense(mesh8):
    x = jax.random.normal(RNG, (8, 8, D))
    p = _params(_cfg("sort"))
    y = {}
    y["grouped_tp"], _, _ = _apply(mesh8, _cfg("grouped"), p, x, tp="data")
    y["sort_tp"], _, _ = _apply(mesh8, _cfg("sort"), p, x, tp="data")
    y["dense"], _, _ = _apply(mesh8, _cfg("dense"), p, x)
    y["grouped"], _, _ = _apply(mesh8, _cfg("grouped"), p, x)
    for name in ("sort_tp", "dense", "grouped"):
        np.testing.assert_allclose(
            np.asarray(y["grouped_tp"]), np.asarray(y[name]),
            rtol=1e-4, atol=1e-5, err_msg=name)


def test_grouped_tp_matches_sort_tp_bf16(mesh8):
    """f32-accumulated grouped matmuls under TP stay within bf16
    rounding of the sort+TP path."""
    x = jax.random.normal(RNG, (8, 8, D), jnp.bfloat16)
    p = _params(_cfg("sort"), dtype=jnp.bfloat16)
    yg, _, _ = _apply(mesh8, _cfg("grouped"), p, x, tp="data")
    ys, _, _ = _apply(mesh8, _cfg("sort"), p, x, tp="data")
    np.testing.assert_allclose(np.asarray(yg, np.float32),
                               np.asarray(ys, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype,rtol", [
    (jnp.float32, 1e-4), (jnp.bfloat16, 2e-2)])
def test_grouped_tp_gradients_match_sort_tp(mesh8, dtype, rtol):
    """Same loss, same gradients (router AND f-sliced expert weights)
    through the grouped-TP collectives as through the sort-TP pair.

    f32 compares elementwise; bf16 compares norm-wise — the dispatch
    paths round the FFN outputs differently at bf16 ULP and the router
    gradient amplifies that elementwise (the same spread exists between
    sort and grouped WITHOUT TP), but the gradient as a vector must
    stay within bf16 accumulation error."""
    x = jax.random.normal(RNG, (8, 8, D), dtype)
    p = _params(_cfg("sort"), dtype=dtype)

    def loss_fn(cfg):
        def loss(p, v):
            y, aux, _ = moe.sharded_moe_apply(
                mesh8, cfg, p, v, num_experts=E, act="swiglu",
                expert_tp_axis="data")
            return jnp.sum(y.astype(jnp.float32) ** 2) + aux
        return jax.jit(jax.value_and_grad(loss))

    lg, gg = loss_fn(_cfg("grouped"))(p, x)
    ls, gs = loss_fn(_cfg("sort"))(p, x)
    np.testing.assert_allclose(float(lg), float(ls), rtol=rtol)
    for k in p:
        a = np.asarray(gg[k], np.float32)
        b = np.asarray(gs[k], np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-5,
                                       err_msg=k)
        else:
            err = np.linalg.norm(a - b) / np.linalg.norm(b)
            assert err < rtol, (k, err)
        assert np.linalg.norm(a) > 0, k


def test_grouped_tp_is_dropless_where_sort_drops(mesh8):
    """cf=0.25 starves sort+TP; grouped+TP ignores capacity_factor and
    reproduces the unconstrained reference on every token."""
    x = jax.random.normal(RNG, (8, 16, D))
    cfg_g = MoEConfig(num_experts=E, gate="switch", capacity_factor=0.25,
                      dispatch="grouped")
    cfg_ref = MoEConfig(num_experts=E, gate="switch", capacity_factor=16.0,
                        dispatch="sort")
    p = _params(cfg_g)
    yg, _, _ = _apply(mesh8, cfg_g, p, x, tp="data")
    yr, _, _ = _apply(mesh8, cfg_ref, p, x)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yr),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# grouped-TP × grouped-EP on the (data=2, model=2) mesh
# ---------------------------------------------------------------------------

def test_grouped_tp_with_grouped_ep(mesh1, mesh_dm22):
    """TP over ``data`` composed with the grouped AllToAll over
    ``model`` reproduces both the single-device grouped numerics and
    the sort+TP path on the same mesh."""
    cfg = _cfg("grouped")
    p = _params(cfg)
    x = jax.random.normal(RNG, (4, 16, D))
    y1, _, _ = _apply(mesh1, cfg, p, x)
    ytp, _, _ = _apply(mesh_dm22, cfg, p, x, tp="data")
    ysort, _, _ = _apply(mesh_dm22, _cfg("sort"), p, x, tp="data")
    np.testing.assert_allclose(np.asarray(ytp), np.asarray(y1),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ytp), np.asarray(ysort),
                               rtol=1e-4, atol=1e-5)


def test_grouped_tp_ep_hierarchical_equals_flat(mesh8):
    """TP × grouped-EP × the paper's two-stage a2a (model=4 →
    inner=2 × outer=2): identical output to the flat exchange."""
    x = jax.random.normal(RNG, (8, 8, D))
    cfgf = _cfg("grouped", gate="switch", top_k=1)
    cfgh = _cfg("grouped", gate="switch", top_k=1,
                a2a="hierarchical", a2a_inner=2)
    p = _params(cfgf)
    yf, _, _ = _apply(mesh8, cfgf, p, x, tp="data")
    yh, _, _ = _apply(mesh8, cfgh, p, x, tp="data")
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yh),
                               rtol=1e-5, atol=1e-6)


def test_grouped_tp_ep_gradients_flow(mesh_dm22):
    cfg = _cfg("grouped", gate="switch", top_k=1, capacity_factor=4.0)
    p = _params(cfg)
    x = jax.random.normal(RNG, (4, 16, D))

    def loss(p, v):
        y, aux, _ = moe.sharded_moe_apply(
            mesh_dm22, cfg, p, v, num_experts=E, act="swiglu",
            expert_tp_axis="data")
        return jnp.sum(y ** 2) + aux

    g = jax.jit(jax.grad(loss))(p, x)
    for k, v in g.items():
        assert bool(jnp.all(jnp.isfinite(v))), k
        assert float(jnp.linalg.norm(v)) > 0, k


def test_grouped_tp_pallas_matches_jnp(mesh_dm22):
    """The Pallas gather/grouped-matmul kernels drive the TP×EP path
    end to end and agree with the jnp/ragged path, value and grad."""
    res = {}
    for pall in (False, True):
        cfg = _cfg("grouped", gate="switch", top_k=1, capacity_factor=2.0,
                   use_pallas_gate=pall)
        p = _params(cfg)
        x = jax.random.normal(RNG, (2, 16, D))

        def loss(p, v, cfg=cfg):
            y, aux, _ = moe.sharded_moe_apply(
                mesh_dm22, cfg, p, v, num_experts=E, act="swiglu",
                expert_tp_axis="data")
            return jnp.sum(y ** 2) + aux

        l, g = jax.jit(jax.value_and_grad(loss))(p, x)
        res[pall] = (float(l), float(jnp.linalg.norm(g["gate_w"])),
                     float(jnp.linalg.norm(g["w_up"])))
    np.testing.assert_allclose(res[False], res[True], rtol=1e-4)


def test_grouped_tp_tight_bound_stays_finite(mesh_dm22):
    """A binding grouped-EP segment bound under TP behaves like sort
    capacity: finite output, dropped rows ride the residual."""
    cfg = _cfg("grouped", gate="switch", top_k=1,
               grouped_ep_bound_factor=1.0)
    p = _params(cfg)
    x = jax.random.normal(RNG, (8, 16, D))
    y, aux, _ = _apply(mesh_dm22, cfg, p, x, tp="data")
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert bool(jnp.isfinite(aux))


def test_grouped_tp_token_padding_path(mesh_dm22):
    """T % n_dev != 0 (decode): virtual-expert rows stay out of the TP
    segment merge; output finite and equal to the sort+TP path."""
    cfg_g = _cfg("grouped", gate="switch", top_k=1)
    cfg_s = _cfg("sort", gate="switch", top_k=1)
    p = _params(cfg_g)
    x = jax.random.normal(RNG, (3, 1, D))
    yg, _, _ = _apply(mesh_dm22, cfg_g, p, x, tp="data")
    ys, _, _ = _apply(mesh_dm22, cfg_s, p, x, tp="data")
    assert yg.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(yg)))
    np.testing.assert_allclose(np.asarray(yg), np.asarray(ys),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# quantized exchange wire (PR 10) × TP: the int8/fp8 payload composes
# with expert tensor parallelism on the (data=2, model=2) mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qdt,out_tol,grad_tol", [
    ("int8", 5e-2, 1e-1),
    ("float8_e4m3fn", 1.5e-1, 3e-1),
])
def test_grouped_tp_ep_quantized_payload_fwd_and_grad(mesh_dm22, qdt,
                                                      out_tol, grad_tol):
    """Quantization touches only the model-axis exchange, so under TP
    over ``data`` the f-sliced grouped matmuls and their collectives
    must be reused unchanged: value and parameter gradients stay within
    the same per-dtype budgets as the EP-only cells (see
    test_grouped.QWIRE_TOLS for the measured medians)."""
    x = jax.random.normal(RNG, (4, 16, D))
    runs = {}
    for pd in (None, qdt):
        cfg = _cfg("grouped", gate="switch", top_k=1, capacity_factor=4.0,
                   payload_dtype=pd)
        p = _params(cfg)

        def loss(p, v, cfg=cfg):
            y, aux, _ = moe.sharded_moe_apply(
                mesh_dm22, cfg, p, v, num_experts=E, act="swiglu",
                expert_tp_axis="data")
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
