"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode checks what a kernel computes, not whether Mosaic accepts it:
block shapes that break the (8, 128) rule, unaligned slices, or VMEM
overruns are refused only by the TPU compiler.  These tests compile each
kernel of the served search path for a described (not attached) v5e chip at
the widths of an ann-benchmarks sift-128 deployment (d = 128, window =
row_cap = 32, k = 10, a 1M-row store) and check that the program holds a
Mosaic kernel (`tpu_custom_call`); the sharded store's search is compiled
for the whole described 2x2 host.  Nothing runs; a compile is not a chip
run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.grid import GridConfig
from repro.kernels import ops
from repro.kernels.csr_candidate_topk import SLAB_BUDGET_BYTES, rows_in_flight
from repro.kernels.csr_candidate_topk_q8 import q8_store_rows

N, D, K = 1 << 20, 128, 10
CFG = GridConfig(grid_size=4096, window=32, row_cap=32)
W, RC = CFG.window, CFG.row_cap


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four chips of a described v5e:2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for the described chip cannot be read back from the
    # persistent cache without a chip; keep it out of the cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b", [64, 256])
def test_tile_count_multilevel_compiles(one_chip, b):
    n_tiles = sum(nb * nb for nb in CFG.level_nblks)
    _compile(
        one_chip,
        lambda t, q, r, lv, act: ops.tile_count_multilevel(
            t, q, r, lv, CFG.tile, CFG.level_nblks, interpret=False,
            active=act,
        ),
        ((n_tiles, CFG.n_channels, CFG.tile, CFG.tile), jnp.int32),
        ((b, 2), jnp.float32), ((b,), jnp.float32), ((b,), jnp.int32),
        ((b,), jnp.bool_),
    )


@pytest.mark.parametrize("b", [1, 64, 256])
def test_csr_candidate_topk_compiles(one_chip, b):
    _compile(
        one_chip,
        lambda st, s, e, q: ops.csr_candidate_topk(
            st, s, e, q, K, N, RC, interpret=False
        ),
        ((N, D), jnp.float32), ((b, W), jnp.int32), ((b, W), jnp.int32),
        ((b, D), jnp.float32),
    )


def test_rows_in_flight_sift_and_wide():
    """A whole sift window per slab; at d = 960 (gist) the two slabs stay
    within the budget, so the rows go through groups."""
    assert rows_in_flight(W, RC, D) == W
    depth = rows_in_flight(W, RC, 960)
    assert 1 <= depth < W
    assert 2 * depth * RC * 1024 * 4 <= SLAB_BUDGET_BYTES


@pytest.mark.parametrize("b,rerank_k", [(64, 4 * K), (256, 4 * K), (64, W * RC)])
def test_csr_shortlist_q8_compiles(one_chip, b, rerank_k):
    rows = q8_store_rows(N, RC)
    _compile(
        one_chip,
        lambda st, sc, s, e, q: ops.csr_shortlist_q8(
            st, sc, s, e, q, rerank_k, N, RC, interpret=False
        ),
        ((rows, D), jnp.int8), ((rows,), jnp.float32), ((b, W), jnp.int32),
        ((b, W), jnp.int32), ((b, D), jnp.float32),
    )


@pytest.mark.parametrize("b,c", [(64, 4 * K), (256, 4 * K), (64, W * RC)])
def test_candidate_topk_compiles(one_chip, b, c):
    _compile(
        one_chip,
        lambda cand, v, q: ops.candidate_topk(
            cand, v, q, K, d_chunk=D, interpret=False
        ),
        ((b, c, D), jnp.float32), ((b, c), jnp.bool_), ((b, D), jnp.float32),
    )


def test_sharded_search_compiles_for_2x2(v5e_2x2):
    """The sharded store's search over a 2x2 v5e host: the Mosaic kernels
    inside shard_map, one chip's share (a 1M-row store) per shard, and the
    cross-chip merge as an all-gather."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import distributed as dist
    from repro.core.grid import GridIndex
    from repro.core.projection import Projection

    mesh = Mesh(np.asarray(v5e_2x2), (dist.SHARD_AXIS,))
    s = mesh.shape[dist.SHARD_AXIS]
    stacked = NamedSharding(mesh, P(dist.SHARD_AXIS))
    cells = CFG.padded_size ** 2

    def shard(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct((s,) + shape, dt, sharding=stacked)

    n_tiles = sum(nb * nb for nb in CFG.level_nblks)
    index = GridIndex(
        proj=Projection(shard((D, 2), jnp.float32), shard((2,), jnp.float32),
                        shard((2,), jnp.float32)),
        points_sorted=shard((N, D), jnp.float32),
        coords_sorted=shard((N, 2), jnp.float32),
        labels_sorted=shard((N,)),
        ids_sorted=shard((N,)),
        offsets=shard((cells + 1,)),
        pyramid=tuple(shard((CFG.padded_size >> lv, CFG.padded_size >> lv,
                             CFG.n_channels)) for lv in range(CFG.levels)),
        pyr_tiles=shard((n_tiles, CFG.n_channels, CFG.tile, CFG.tile)),
        global_offsets=shard((cells + 1,)),
        global_cells=shard((s * N,)),
    )
    queries = jax.ShapeDtypeStruct((256, D), jnp.float32,
                                   sharding=NamedSharding(mesh, P()))
    hlo = dist._sharded_call.lower(
        index, CFG, queries, K, mesh, dist.SHARD_AXIS, "refined", False,
        None, False, "search",
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" in hlo
