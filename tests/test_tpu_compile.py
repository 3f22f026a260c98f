"""Ahead-of-time compiles for a described TPU v5e chip (no chip attached).

Every Pallas kernel is compiled by Mosaic with ``interpret=False`` at the
serving widths (N = 2^20 rows, C in {64, 128}; the drafter's walk at its
8192-row table), and the one-chip sharded update and query programs are
compiled whole at 2^20 x 64.  A kernel that only passes interpret mode
fails here: unaligned blocks, missing lowerings, too much VMEM.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a test worker that loads
it keeps it until it exits.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import mcprioq as mc
from repro.core import sharded as sh
from repro.kernels import cdf_gather as cgk
from repro.kernels import cdf_query as cdfk
from repro.kernels import oddeven as oek
from repro.kernels import ops
from repro.kernels import probe as prk
from repro.kernels import slab_update as suk
from repro.kernels import walk as wkk

N = 2 ** 20          # rows of the chip-scale state
B = 2048             # update/query batch
DRAFT_ROWS = 8192    # the drafter chain of launch/serve.py
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a described chip's executables cannot be read back from a cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert "tpu_custom_call" in compiled.as_text()
    assert total <= HBM_BYTES, total
    return compiled


def _kernel_case(name, c, spec):
    """(fn, arg shapes) of one kernel at N rows x C slots."""
    i32 = lambda shape: spec(shape, jnp.int32)
    if name == "slab_update":
        return (lambda r, d, w, ds, cn, t: suk.slab_update_pallas(
                    r, d, w, ds, cn, t, interpret=False),
                (i32((B,)), i32((B,)), i32((B,)), i32((N, c)), i32((N, c)),
                 i32((N,))))
    if name == "oddeven":
        return (lambda cnt, order: oek.oddeven_pallas(cnt, order, passes=1,
                                                      interpret=False),
                (i32((N, c)), i32((N, c))))
    if name == "dh_find":
        h = mc.MCConfig(capacity=c).resolved_dst_table_size()
        return (lambda r, k, tk, tv: prk.probe_find_pallas(
                    r, k, tk, tv, interpret=False),
                (i32((B,)), i32((B,)), i32((N, h)), i32((N, h))))
    if name == "ht_find":
        t = mc.MCConfig(num_rows=N).resolved_table_size()
        return (lambda k, tk, tv: prk.probe_find_pallas(
                    jnp.zeros_like(k), k, tk[None], tv[None],
                    interpret=False),
                (i32((B,)), i32((t,)), i32((t,))))
    if name == "cdf_query":
        chunks = cdfk.auto_chunks(c, 0)
        return (lambda cn, d, t: cdfk.cdf_query_pallas(
                    cn, d, t, 0.9, chunks=chunks, interpret=False),
                (i32((B, c)), i32((B, c)), i32((B,))))
    if name == "cdf_gather":
        chunks = cdfk.auto_chunks(c, 0)
        return (lambda r, f, cn, d, o, t: cgk.cdf_query_fused_pallas(
                    r, f, cn, d, o, t, 0.9, chunks=chunks, interpret=False),
                (i32((B,)), i32((B,)), i32((N, c)), i32((N, c)),
                 i32((N, c)), i32((N,))))
    assert name == "walk"
    t = mc.MCConfig(num_rows=DRAFT_ROWS).resolved_table_size()
    return (lambda w, hk, hv, cn, d, o: wkk.draft_walk_pallas(
                w, hk, hv, cn, d, o, interpret=False),
            (i32((256, 2)), i32((t,)), i32((t,)), i32((DRAFT_ROWS, c)),
             i32((DRAFT_ROWS, c)), i32((DRAFT_ROWS,))))


@pytest.mark.parametrize("c", [64, 128])
@pytest.mark.parametrize("name", ["slab_update", "oddeven", "dh_find",
                                  "ht_find", "cdf_query", "cdf_gather",
                                  "walk"])
def test_kernel_compiles_for_v5e(one_chip, name, c):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    fn, args = _kernel_case(name, c, spec)
    _compile(fn, *args)


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer ``impl='auto'`` dispatch to the compiled kernels while a
    program is traced for the described chip; traces made either way are
    dropped so no other test reuses them."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _one_chip_program(topo, program):
    """The one-chip sharded update or query program, compiled whole at
    2^20 x 64."""
    mesh = Mesh(np.array(topo.devices[:1]), ("shard",))
    scfg = sh.ShardedConfig(
        base=mc.MCConfig(num_rows=N, capacity=64, sort_passes=1),
        num_shards=1)
    shard = NamedSharding(mesh, P("shard"))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype,
                                       sharding=shard),
        jax.eval_shape(lambda: mc.init(scfg.base)))
    batch = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=shard)
    if program == "update":
        return _compile(sh.make_update_fn(scfg, mesh), state, batch, batch,
                        batch)
    return _compile(sh.make_query_fn(scfg, mesh, 0.9, 16), state, batch)


@pytest.mark.parametrize("program", ["update", "query"])
def test_sharded_program_compiles_for_one_v5e_chip(topo, on_tpu, program):
    _one_chip_program(topo, program)


def test_update_program_gathers_no_whole_state(topo, on_tpu):
    """The odd-even sort gathers counts into order position inside its
    kernel: no XLA gather of the update program yields N x 64 elements."""
    hlo = _one_chip_program(topo, "update").as_text()
    gathers = re.findall(r"= \w+\[([\d,]*)\][^ ]* gather\(", hlo)
    assert gathers, "no gather found: the HLO text changed form"
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
             for dims in gathers]
    assert max(sizes) < N * 64, sorted(sizes)
