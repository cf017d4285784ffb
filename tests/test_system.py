"""System-level behaviour: multi-device training with the production
sharding rules (8 fake devices), and a mini dry-run (lower+compile)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.config import TrainConfig
from repro.data import SyntheticLM
from repro.launch import mesh as mesh_lib
from repro.training import make_train_step
from repro.training.train_step import init_train_state

RNG = jax.random.PRNGKey(0)


def test_sharded_train_matches_single_device(mesh1, mesh8):
    """The production sharding rules change nothing numerically."""
    cfg = configs.smoke_config("dbrx-132b").replace(dtype="float32")
    tcfg = TrainConfig(total_steps=3, warmup_steps=0)
    ds = SyntheticLM(cfg, batch=8, seq_len=16)
    b = ds.next_batch(0)

    results = {}
    for name, mesh in (("1dev", mesh1), ("8dev", mesh8)):
        state = init_train_state(RNG, cfg, tcfg)
        if name == "8dev":
            sh = mesh_lib.state_shardings(mesh, jax.eval_shape(lambda: state))
            state = jax.device_put(state, sh)
            b_sh = mesh_lib.batch_shardings(mesh, jax.eval_shape(lambda: b))
            bb = jax.device_put(b, b_sh)
        else:
            bb = b
        step = jax.jit(make_train_step(cfg, tcfg, mesh))
        state, m = step(state, bb, RNG)
        results[name] = (float(m["ce"]),
                         np.asarray(jax.device_get(state.params["final_norm"])))
    np.testing.assert_allclose(results["1dev"][0], results["8dev"][0],
                               rtol=1e-4)
    np.testing.assert_allclose(results["1dev"][1], results["8dev"][1],
                               rtol=1e-3, atol=1e-5)


def test_mini_dryrun_lowers_and_compiles(mesh8):
    """lower().compile() with sharded ShapeDtypeStructs — the same path
    the 512-device production dry-run takes."""
    cfg = configs.smoke_config("llama4-maverick-400b-a17b")
    tcfg = TrainConfig(remat="block")
    state_shapes = jax.eval_shape(
        lambda r: init_train_state(r, cfg, tcfg), jax.random.key(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_shapes, mesh_lib.state_shardings(mesh8, state_shapes))
    from repro.data.pipeline import make_batch_specs
    from repro.core.config import ShapeConfig
    shape = ShapeConfig("mini", 32, 8, "train")
    batch = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        make_batch_specs(cfg, shape),
        mesh_lib.batch_shardings(mesh8, make_batch_specs(cfg, shape)))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh8, P()))
    fn = make_train_step(cfg, tcfg, mesh8)

    def step(state, batch, rng_raw):
        return fn(state, batch, jax.random.wrap_key_data(rng_raw))

    compiled = jax.jit(step, donate_argnums=(0,)).lower(state, batch, rng).compile()
    ca = compiled.cost_analysis()
    assert ca.get("flops", 0) > 0
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes > 0


def test_collective_parser():
    from repro.launch.dryrun import parse_collectives
    hlo = """
  %all-to-all.1 = bf16[8,1344,6144]{2,1,0} all-to-all(%x), channel_id=1, replica_groups=[16,16]<=[256], dimensions={0}
  %all-reduce.2 = f32[128]{0} all-reduce(%y), channel_id=2, replica_groups=[1,256]<=[256], to_apply=%add
  %ag = (bf16[64,32]{1,0}, bf16[64,32]{1,0}) all-gather(%a, %b), channel_id=3, replica_groups=[16,16]<=[256], dimensions={0}
"""
    out = parse_collectives(hlo)
    assert out["count"] == 3
    a2a = [o for o in out["ops"] if o["kind"] == "all-to-all"][0]
    assert a2a["group"] == 16
    assert a2a["result_bytes"] == 8 * 1344 * 6144 * 2
    ar = [o for o in out["ops"] if o["kind"] == "all-reduce"][0]
    np.testing.assert_allclose(ar["wire_bytes"], 2 * 512 * 255 / 256)
    ag = [o for o in out["ops"] if o["kind"] == "all-gather"][0]
    assert ag["result_bytes"] == 2 * 64 * 32 * 2


def test_fit_spec_drops_nondivisible():
    mesh = mesh_lib.make_smoke_mesh((2, 4))
    s = mesh_lib.fit_spec(mesh, P("data", "model"), (6, 92553))
    assert s.spec == P("data", None)
    s = mesh_lib.fit_spec(mesh, P(("data", "model"),), (8,))
    assert s.spec == P(("data", "model"))
    s = mesh_lib.fit_spec(mesh, P(("data", "model"),), (4,))
    assert s.spec == P(None)
