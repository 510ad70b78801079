import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""ARCADE data-plane dry-run: lower + compile the shard_map scatter-gather
query kernels (core/distributed.py) on the production meshes — the
distribution proof for the paper's own layer (the LM-zoo dry-run is
launch/dryrun.py).

  PYTHONPATH=src python -m repro.launch.dryrun_arcade [--multi-pod]
"""

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed as dist
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh


def run(multi_pod: bool, n_per_shard: int = 1 << 16, dim: int = 128,
        k: int = 100):
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_global = n_per_shard * mesh.devices.size
    name = "2x16x16" if multi_pod else "16x16"

    # shard rows over every axis (segments partitioned store-wide)
    axes_all = P(tuple(mesh.axis_names))
    vec_sh = NamedSharding(mesh, P(tuple(mesh.axis_names), None))
    id_sh = NamedSharding(mesh, axes_all)

    qv = jax.ShapeDtypeStruct((dim,), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    vecs = jax.ShapeDtypeStruct((n_global, dim), jnp.float32,
                                sharding=vec_sh)
    ids = jax.ShapeDtypeStruct((n_global,), jnp.int64, sharding=id_sh)

    shard_axes = tuple(mesh.axis_names)

    def _shardfn(q, v, i):
        d, idx = dist.local_topk(q, v, k)
        lids = i[idx]
        all_d = d
        all_i = lids
        for ax in shard_axes:
            all_d = jax.lax.all_gather(all_d, ax).reshape(-1)
            all_i = jax.lax.all_gather(all_i, ax).reshape(-1)
        neg, pos = jax.lax.top_k(-all_d, k)
        return -neg, all_i[pos]

    fn = jax.shard_map(_shardfn, mesh=mesh,
                       in_specs=(P(), P(shard_axes, None), P(shard_axes)),
                       out_specs=(P(), P()), check_vma=False)
    lowered = jax.jit(fn).lower(qv, vecs, ids)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    roof = rl.analyze(compiled, mesh.devices.size)
    per_dev = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes)
    print(f"[{name}] ARCADE distributed top-{k} over {n_global:,} vectors: "
          f"OK bytes/dev={per_dev / 2**20:.1f}MiB "
          f"c={roof.compute_s * 1e6:.0f}us m={roof.memory_s * 1e6:.0f}us "
          f"k={roof.collective_s * 1e6:.0f}us "
          f"bottleneck={roof.bottleneck}")
    print("  memory_analysis:", ma)
    print("  collectives:", {kk: f"{v:.2e}"
                             for kk, v in roof.coll_by_kind.items()})
    return compiled


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", choices=["no", "yes", "both"],
                    default="both")
    args = ap.parse_args()
    pods = {"no": [False], "yes": [True],
            "both": [False, True]}[args.multi_pod]
    for mp in pods:
        run(mp)


if __name__ == "__main__":
    main()
