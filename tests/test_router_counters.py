"""Routing counters: ``dropped_share`` (assignments dropped over routed)
and ``expert_load_ratio`` (busiest expert over the mean), from the
dispatch plan's counts, and ``grouped_visit_share`` (the grouped Pallas
kernels' grid steps that do work), for the MoE layer and through the
train step."""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import capacity, moe
from repro.core.config import MoEConfig, TrainConfig
from repro.launch import train

D, E, T = 16, 4, 64


def _params(cfg, gate_w):
    p = moe.init_moe_params(jax.random.PRNGKey(0), cfg, D, 32, E, act="relu",
                            dtype=jnp.float32)
    return {**p, "gate_w": gate_w}


def _collapsed():
    """Every token's router logits favour expert 0."""
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (T, D))) + 0.1
    return x, jnp.zeros((D, E)).at[:, 0].set(1.0)


def _uniform():
    """Token t routes to expert t % E."""
    t = jnp.arange(T)
    x = jnp.zeros((T, D)).at[t, t % E].set(1.0)
    return x, jnp.zeros((D, E)).at[jnp.arange(E), jnp.arange(E)].set(1.0)


def _local(cfg, x, w):
    _, _, m = moe.moe_block_local(cfg, _params(cfg, w), x, num_experts=E,
                                  act="relu")
    return {k: float(v) for k, v in m.items()}


def _cfg(dispatch, cf=1.0, **kw):
    return MoEConfig(num_experts=E, gate="switch", capacity_factor=cf,
                     dispatch=dispatch, **kw)


@pytest.mark.parametrize("dispatch", ["sort", "dense"])
def test_collapsed_router_drops_past_capacity(dispatch):
    cfg = _cfg(dispatch)
    C = capacity.expert_capacity(cfg, T, E)
    assert C < T
    m = _local(cfg, *_collapsed())
    assert m["dropped_share"] == pytest.approx((T - C) / T)
    assert E * m["expert_load_max"] == pytest.approx(E)


@pytest.mark.parametrize("dispatch", ["sort", "dense", "grouped"])
def test_uniform_router_reads_one_and_drops_nothing(dispatch):
    m = _local(_cfg(dispatch), *_uniform())
    assert m["dropped_share"] == 0
    assert E * m["expert_load_max"] == pytest.approx(1.0)


def test_grouped_path_is_dropless_and_agrees_on_the_load():
    grouped = _local(_cfg("grouped"), *_collapsed())
    sort = _local(_cfg("sort"), *_collapsed())
    assert grouped["dropped_share"] == 0
    assert grouped["expert_load_max"] == sort["expert_load_max"]


@pytest.mark.parametrize("dispatch,pallas,share", [
    # 64 rows in four experts of 16, row blocks of 24 (padded to 72):
    # expert 0 meets block 0, expert 1 blocks 0 and 1, expert 2 block 1,
    # expert 3 block 2 and the padding: 5 visits of nb + E - 1 = 6 steps
    ("grouped", True, 5 / 6),
    ("grouped", False, 0.0),    # lax.ragged_dot: no grouped Pallas kernel
    ("sort", True, 0.0),
    ("sort", False, 0.0),
    ("dense", False, 0.0),
])
def test_grouped_visit_share(dispatch, pallas, share):
    cfg = _cfg(dispatch, use_pallas_gate=pallas, grouped_block_m=24)
    assert _local(cfg, *_uniform())["grouped_visit_share"] == (
        pytest.approx(share))


def test_grouped_ep_bound_drops_like_capacity(mesh_ep4):
    """Over four expert-parallel ranks, each rank's 16 tokens all want
    expert 0: the sort path keeps C = 8 of them, the grouped exchange's
    bound B = 8 too, so both drop half, counted over the whole mesh."""
    x, w = _collapsed()
    shares = {}
    for cfg in (_cfg("sort"), _cfg("grouped", grouped_ep_bound_factor=1.0)):
        _, _, m = jax.jit(lambda p, v, cfg=cfg: moe.sharded_moe_apply(
            mesh_ep4, cfg, p, v, num_experts=E, act="relu"))(
                _params(cfg, w), x)
        assert E * float(m["expert_load_max"]) == pytest.approx(E)
        shares[cfg.dispatch] = float(m["dropped_share"])
    assert capacity.expert_capacity(_cfg("sort"), T // 4, E) == 8
    assert shares == {"sort": pytest.approx(0.5), "grouped": pytest.approx(0.5)}


@pytest.mark.parametrize("microbatches", [1, 2])
def test_train_step_reports_the_counters(mesh1, microbatches):
    cfg = configs.smoke_config("hetumoe-paper-16e")
    tcfg = TrainConfig(microbatches=microbatches)
    tr = train.build(cfg, tcfg, mesh1, batch=4, seq=32)
    _, m = tr.dispatch(tr.state, 0)
    m = tr.fetch(m)
    n = cfg.moe.num_experts
    assert 1.0 <= m["expert_load_ratio"] <= n
    assert 0.0 <= m["dropped_share"] < 1.0
    assert 0.0 <= m["grouped_visit_share"] <= 1.0


def test_dense_model_has_no_counters(mesh1):
    cfg = configs.smoke_config("starcoder2-3b")
    tr = train.build(cfg, TrainConfig(), mesh1, batch=2, seq=16)
    m = tr.fetch(tr.dispatch(tr.state, 0)[1])
    assert not {"expert_load_ratio", "dropped_share",
                "grouped_visit_share"} & set(m)
