"""GridIndex structural invariants — unit + property (hypothesis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as hst

from repro.core import grid as G
from repro.core import projection as proj_lib


def _build(points, n_classes=0, grid_size=64, labels=None):
    cfg = G.GridConfig(grid_size=grid_size, tile=8, n_classes=n_classes,
                       window=8, row_cap=16, r0=4)
    proj = proj_lib.identity_projection(points)
    return cfg, G.build_index(points, cfg, proj, labels=labels)


def test_invariants_basic(rng):
    pts = jnp.asarray(rng.normal(size=(500, 2)), jnp.float32)
    cfg, idx = _build(pts)
    inv = G.validate_invariants(idx, cfg)
    assert all(inv.values()), inv


def test_csr_matches_counts(rng):
    pts = jnp.asarray(rng.normal(size=(300, 2)), jnp.float32)
    cfg, idx = _build(pts)
    g = cfg.padded_size
    counts = np.asarray(idx.offsets[1:] - idx.offsets[:-1]).reshape(g, g)
    base = np.asarray(G.base_counts(idx))
    np.testing.assert_array_equal(counts, base)


def test_class_channels(rng):
    pts = jnp.asarray(rng.normal(size=(400, 2)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, size=400), jnp.int32)
    cfg, idx = _build(pts, n_classes=3, labels=labels)
    per_class = np.asarray(idx.pyramid[0].sum(axis=(0, 1)))
    expect = np.bincount(np.asarray(labels), minlength=3)
    np.testing.assert_array_equal(per_class, expect)


def test_pyramid_levels_sum(rng):
    pts = jnp.asarray(rng.normal(size=(256, 2)), jnp.float32)
    cfg, idx = _build(pts)
    for lv, arr in enumerate(idx.pyramid):
        assert int(arr.sum()) == 256, f"level {lv} mass"
        assert arr.shape[0] == cfg.padded_size // (1 << lv)


def test_points_sorted_by_cell(rng):
    pts = jnp.asarray(rng.uniform(size=(200, 2)), jnp.float32)
    cfg, idx = _build(pts)
    cid = np.asarray(G.cell_id_of(idx.coords_sorted, cfg.padded_size))
    assert (np.diff(cid) >= 0).all()


def test_ids_are_permutation(rng):
    pts = jnp.asarray(rng.normal(size=(100, 2)), jnp.float32)
    _, idx = _build(pts)
    assert sorted(np.asarray(idx.ids_sorted).tolist()) == list(range(100))


@settings(max_examples=25, deadline=None)
@given(
    n=hst.integers(min_value=1, max_value=200),
    seed=hst.integers(min_value=0, max_value=2**31 - 1),
    d=hst.integers(min_value=2, max_value=5),
)
def test_property_invariants(n, seed, d):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.normal(size=(n, d)) * rng.uniform(0.1, 10), jnp.float32)
    cfg = G.GridConfig(grid_size=32, tile=8, window=8, row_cap=max(16, n), r0=2)
    proj = (proj_lib.identity_projection(pts) if d == 2
            else proj_lib.gaussian_projection(jax.random.PRNGKey(seed), pts))
    idx = G.build_index(pts, cfg, proj)
    inv = G.validate_invariants(idx, cfg)
    assert all(inv.values()), inv


def test_grid_config_levels():
    cfg = G.GridConfig(grid_size=3000, tile=16)
    # padded to tile * 2**(levels-1) >= 3000
    assert cfg.padded_size >= 3000
    assert cfg.padded_size == cfg.tile * (1 << (cfg.levels - 1))
    assert cfg.padded_size // (1 << (cfg.levels - 1)) == cfg.tile


@pytest.mark.parametrize("tile", [0, 1, 2, 3])
def test_grid_config_rejects_degenerate_tile(tile):
    """tile <= 3 breaks level_for_radius's containment guarantee (its
    max(tile - 3, 1) divisor would silently under-select levels)."""
    with pytest.raises(ValueError, match="tile"):
        G.GridConfig(grid_size=64, tile=tile)


def test_grid_config_accepts_min_tile():
    # explicit r0: the default (100) exceeds max_radius on a 64-wide grid
    assert G.GridConfig(grid_size=64, tile=4, r0=8).tile == 4


@pytest.mark.parametrize("r0", [0, -1, -100])
def test_grid_config_rejects_nonpositive_r0(r0):
    """The radius loop used to jnp.clip a bad r0 silently — a typo'd start
    radius ran with a DIFFERENT schedule than configured.  Rejected eagerly
    now, like tile/metric/counter."""
    with pytest.raises(ValueError, match="r0"):
        G.GridConfig(grid_size=64, tile=8, r0=r0)


def test_grid_config_rejects_r0_beyond_max_radius():
    cfg_probe = G.GridConfig(grid_size=64, tile=8, r0=8)
    too_big = cfg_probe.max_radius + 1
    with pytest.raises(ValueError, match="max_radius"):
        G.GridConfig(grid_size=64, tile=8, r0=too_big)
    # the boundary itself is legal: max_radius is countable from the top tile
    assert G.GridConfig(grid_size=64, tile=8,
                        r0=cfg_probe.max_radius).r0 == cfg_probe.max_radius
    assert G.GridConfig(grid_size=64, tile=8, r0=1).r0 == 1


def test_flattened_tiles_layout(rng):
    """pyr_tiles is the level-major T-tiling of the pyramid: tile (bx, by)
    of level l lives at offset_l + bx * nblk_l + by."""
    pts = jnp.asarray(rng.normal(size=(300, 2)), jnp.float32)
    cfg, idx = _build(pts)
    assert idx.pyr_tiles.shape == (
        sum(nb * nb for nb in cfg.level_nblks), 1, cfg.tile, cfg.tile
    )
    off = 0
    for lv, arr in enumerate(idx.pyramid):
        nb = arr.shape[0] // cfg.tile
        assert nb == cfg.level_nblks[lv]
        for bx, by in ((0, 0), (nb - 1, 0), (nb - 1, nb - 1)):
            want = arr[bx * cfg.tile:(bx + 1) * cfg.tile,
                       by * cfg.tile:(by + 1) * cfg.tile]
            got = jnp.transpose(idx.pyr_tiles[off + bx * nb + by], (1, 2, 0))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        off += nb * nb
    # total mass is preserved level by level
    assert int(idx.pyr_tiles.sum()) == 300 * cfg.levels
