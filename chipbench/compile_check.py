#!/usr/bin/env python3
"""Compile each cell's train step for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python chipbench/compile_check.py [cell ...]

For every cell named (default: all of BENCHMARK.json) this builds the
step that ``program.Program`` runs, on a mesh of a described ``v5e:2x2``
topology with the cell's shape, and prints the compiler's memory plan
per device (arguments, temporaries, outputs, aliased) and the step's
all-to-all bytes.  Nothing runs: it says what the TPU compiler accepts
and plans, not what a chip measures.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def compile_cell(name: str, topo) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench import hlo, program, spec
    from repro.launch import mesh as mesh_lib
    from repro.training.train_step import init_train_state, make_train_step

    cell = spec.load_cell(name)
    cfg, tcfg = program.model_config(cell), program.train_config(cell)
    n = int(np.prod(cell.mesh))
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:n]).reshape(cell.mesh),
                             ("data", "model"))
    rng = jax.ShapeDtypeStruct((2,), jax.numpy.uint32,
                               sharding=NamedSharding(mesh, P()))
    shapes = jax.eval_shape(lambda r: init_train_state(r, cfg, tcfg),
                            jax.random.PRNGKey(0))
    state_sh = mesh_lib.state_shardings(mesh, shapes)
    state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, state_sh)
    B, S = cell.batch, cell.seq
    batch = {k: jax.ShapeDtypeStruct((B, S), dt) for k, dt in (
        ("inputs", np.int32), ("targets", np.int32), ("loss_mask", np.float32))}
    bsh = mesh_lib.batch_shardings(mesh, batch)
    batch = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), batch, bsh)
    step = jax.jit(make_train_step(cfg, tcfg, mesh),
                   out_shardings=(state_sh, NamedSharding(mesh, P())),
                   donate_argnums=(0,))
    t0 = time.perf_counter()
    compiled = step.lower(state, batch, rng).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    return {"cell": name, "compile_s": round(time.perf_counter() - t0, 1),
            "per_device_gib": {
                "arguments": ma.argument_size_in_bytes / 2 ** 30,
                "temporaries": ma.temp_size_in_bytes / 2 ** 30,
                "outputs": ma.output_size_in_bytes / 2 ** 30,
                "aliased": ma.alias_size_in_bytes / 2 ** 30,
                "plan": hlo.plan_bytes(ma) / 2 ** 30},
            "all_to_all_mib_per_step": hlo.all_to_all_bytes(text) / 2 ** 20,
            "kernels": sorted({n.split("/")[-2] for n in hlo.op_names(text).values()
                               if n.endswith("pallas_call")})}


def main(argv) -> int:
    from jax.experimental import topologies
    from chipbench import spec
    from repro.kernels import ops as kops

    kops.INTERPRET = False          # compile the kernels, do not interpret
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or [w["name"] for w in spec.load_benchmark()["workloads"]]
    for name in names:
        print(json.dumps(compile_cell(name, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
