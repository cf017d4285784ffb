"""Paper Fig. 7 — hierarchical vs flat AllToAll.

Two views:
  (a) α–β cost model in the PAPER's regime (N nodes × 8 GPUs, PCIe +
      one 100 Gb NIC) — reproduces the claimed 1.66×(4×8) / 2×(8×8)
      speedups from message aggregation.
  (b) TPU-adapted regime: the same two-stage factoring across a v5e
      mesh axis with an ICI fast dim and a DCN-grade slow dim.
  (c) functional wall time on 8 fake CPU devices (structure only).
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, timeit
from repro.core import alltoall
from repro.core.alltoall import cost_flat, cost_hierarchical
from repro.launch.mesh import parse_fabric


def run(paper: bool = False):
    B = 16e6                                      # paper: ~16 MB per GPU
    _, (pcie, eth100) = parse_fabric("pcie_eth100")
    _, (ici, dcn) = parse_fabric("ici_dcn")
    for N, G in [(2, 8), (4, 8), (8, 8), (16, 8)]:
        f = cost_flat(B, N, G, pcie, eth100)
        h = cost_hierarchical(B, N, G, pcie, eth100)
        emit(f"a2a/model/gpu-{N}x{G}", h * 1e6,
             f"flat_us={f * 1e6:.0f},speedup={f / h:.2f}x"
             f"{',paper_claims=1.66x' if N == 4 else ''}"
             f"{',paper_claims=2x' if N == 8 else ''}")
    # TPU adaptation: slow dim = DCN (pod boundary), fast dim = ICI
    for N, G in [(2, 16), (4, 16)]:
        f = cost_flat(B, N, G, ici, dcn)
        h = cost_hierarchical(B, N, G, ici, dcn)
        emit(f"a2a/model/tpu-{N}pods-x{G}", h * 1e6,
             f"flat_us={f * 1e6:.0f},speedup={f / h:.2f}x")

    # functional path on 8 fake devices
    if len(jax.devices()) >= 8:
        import numpy as np
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:8]).reshape(8),
                                 ("model",))
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 64, 128))
        flat = jax.jit(jax.shard_map(
            lambda v: alltoall.flat_all_to_all(v, "model"), mesh=mesh,
            in_specs=P("model"), out_specs=P("model"), check_vma=False))
        hier = jax.jit(jax.shard_map(
            lambda v: alltoall.hierarchical_all_to_all(v, "model", inner=4,
                                                       outer=2),
            mesh=mesh, in_specs=P("model"), out_specs=P("model"),
            check_vma=False))
        emit("a2a/functional/flat-8dev", timeit(flat, x), "")
        emit("a2a/functional/hier-8dev", timeit(hier, x),
             "cpu-emulated; see alpha-beta model for fabric-level numbers")


if __name__ == "__main__":
    run()
