"""Every Pallas kernel of the main path compiles for a TPU v5e at the
paper's widths (d = f = 2048, 16 experts, 8192 tokens, 16 heads of 128,
seq 1024), with ``interpret=False`` — no chip needed.

The TPU compiler refuses what the interpreter accepts: block shapes that
are not (8, 128)-aligned, more scoped VMEM than a kernel may use, vector
loads from SMEM, unaligned HBM slices.  Each case is a kernel-level
compile of a second or two.  The topology is described inside a fixture
(only the worker that runs this file loads the TPU compiler).  One
smoke-width train step compiles whole, to check where its flash kernels
sit among the step's named scopes.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import grouped_ffn as gffn
from repro.kernels import layout_transform as lt
from repro.kernels import topk_gate as tg

M, D, F, E = 8192, 2048, 2048, 16          # tokens, d_model, d_ff, experts
B, H, S, HD = 8, 16, 1024, 128             # batch, heads, seq, head dim


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flash(q, k, v, pos):
    return fa.flash_attention(q, k, v, pos, pos, HD ** -0.5, True, None,
                              None, 512, False)


def _flash_grads(q, k, v, pos):
    loss = lambda q, k, v: jnp.sum(_flash(q, k, v, pos).astype(jnp.float32))
    return jax.grad(loss, (0, 1, 2))(q, k, v)


def _offsets(sizes):
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(sizes).astype(jnp.int32)])


# name → (function, argument shapes as (shape, dtype))
_QKV = ((B, H, S, HD), jnp.bfloat16)
CASES = {
    "flash_fwd": (_flash, [_QKV, _QKV, _QKV, ((S,), jnp.int32)]),
    "flash_fwd_bwd": (_flash_grads, [_QKV, _QKV, _QKV, ((S,), jnp.int32)]),
    "grouped_fwd": (
        lambda x, w, s: gffn._grouped_matmul_impl(
            x, w, _offsets(s), interpret=False),
        [((M, D), jnp.bfloat16), ((E, D, F), jnp.bfloat16),
         ((E,), jnp.int32)]),
    "grouped_dlhs": (
        lambda g, w, s: gffn._grouped_matmul_impl(
            g, w, _offsets(s), interpret=False, transpose_rhs=True),
        [((M, F), jnp.bfloat16), ((E, D, F), jnp.bfloat16),
         ((E,), jnp.int32)]),
    "grouped_drhs": (
        lambda x, g, s: gffn._grouped_drhs_impl(
            x, g, _offsets(s), interpret=False),
        [((M, D), jnp.bfloat16), ((M, F), jnp.bfloat16), ((E,), jnp.int32)]),
    "topk_gate": (lambda x: tg.fused_topk_gate(x, 2, interpret=False),
                  [((M, E), jnp.float32)]),
    "gather_rows": (lambda src, idx: lt.gather_rows(src, idx, False),
                    [((M, D), jnp.bfloat16), ((M,), jnp.int32)]),
    "scatter_add_rows": (
        lambda g, idx: lt.scatter_add_rows(g, idx, M, interpret=False),
        [((M, D), jnp.bfloat16), ((M,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
    # an XLA-side temporary larger than the chip would not run either
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


def test_train_step_keeps_the_flash_kernel_under_attention(topo, monkeypatch):
    """The smoke-width train step at seq 1024 (the flash path), compiled
    for a v5e: the kernels' ``pallas_vmem/pallas_call``, forward and
    backward, sit directly under the step's ``attention`` scope, where
    the benchmark's ``flash_attn_ms`` and ``attn_block_ms`` find them."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.core.config import TrainConfig
    from repro.kernels import ops as kops
    from repro.launch import mesh as mesh_lib
    from repro.training import make_train_step
    from repro.training.train_step import init_train_state

    monkeypatch.setattr(kops, "INTERPRET", False)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                             ("data", "model"))
    cfg, tcfg = configs.smoke_config("hetumoe-paper-16e"), TrainConfig()
    shapes = jax.eval_shape(lambda r: init_train_state(r, cfg, tcfg),
                            jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, mesh_lib.state_shardings(mesh, shapes))
    rep = NamedSharding(mesh, P())
    tok = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=rep)
    batch = {"inputs": tok, "targets": tok,
             "loss_mask": jax.ShapeDtypeStruct((1, S), jnp.float32,
                                               sharding=rep)}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    text = jax.jit(make_train_step(cfg, tcfg, mesh)).lower(
        state, batch, key).compile().as_text()
    kernels = set(re.findall(r'op_name="([^"]*pallas_vmem/pallas_call)"',
                             text))
    assert kernels and all("/attention/pallas_vmem/pallas_call" in k
                           for k in kernels), kernels
    assert any(k.startswith("jit(train_step)/transpose(") for k in kernels)
