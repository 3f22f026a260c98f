#!/usr/bin/env python3
"""Compile every program a cell runs for a described (not attached) v5e,
at the cell's real sizes, and print each one's memory analysis.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py rec-1m rec-8m-x4

Nothing runs, so this shows only what the chip's compiler refuses (a
kernel it cannot lower, a program that does not fit) and the bytes it
plans; it is no chip run.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(names) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.core import mcprioq as mc
    from repro.core import sharded as sh
    from repro.core.hashtable import HashTable
    from repro.core.slab import Slabs
    from repro.kernels import ops
    import warm

    jax.config.update("jax_enable_compilation_cache", False)
    ops._on_tpu = lambda: True          # lower Pallas kernels with Mosaic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        s = cfg["serve"]["num_shards"]
        mesh = jax.sharding.Mesh(np.array(topo.devices[:s]), ("shard",))
        shard = NamedSharding(mesh, P("shard"))
        rep = NamedSharding(mesh, P())
        base = mc.MCConfig(**cfg["mc"])
        scfg = sh.ShardedConfig(base=base, num_shards=s,
                                bucket_factor=cfg["serve"]["bucket_factor"])
        st = jax.eval_shape(lambda: jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (s,) + x.shape),
            mc.init(base)))
        st = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard),
            st)

        def arr(n, sh_=shard, dt=jnp.int32):
            return jax.ShapeDtypeStruct((n,), dt, sharding=sh_)

        b, q = cfg["batch"], 64
        progs = {
            "warm": warm.make_program(cfg, mesh, (mc.MCState, HashTable,
                                                  Slabs), compat.shard_map
                                      ).lower(arr(2, rep, jnp.uint32),
                                              arr(s)),
            "update": sh.make_update_fn(scfg, mesh).lower(st, arr(b), arr(b),
                                                          arr(b)),
            "maintain": sh.make_maintain_fn(
                scfg, mesh, cfg["serve"]["decay_threshold"]).lower(st),
            "query": sh.make_query_fn(scfg, mesh, cfg["serve"]["threshold"],
                                      cfg["serve"]["max_items"]).lower(
                st, arr(q)),
            "topn": sh.make_topn_fn(scfg, mesh, cfg["serve"]["topn"]).lower(
                st),
        }
        for pname, low in progs.items():
            m = low.compile().memory_analysis()
            print(f"{name} {pname}: arguments {m.argument_size_in_bytes} B, "
                  f"outputs {m.output_size_in_bytes} B, aliased "
                  f"{m.alias_size_in_bytes} B, temporaries "
                  f"{m.temp_size_in_bytes} B", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or ["rec-1m", "rec-8m-x4"]))
