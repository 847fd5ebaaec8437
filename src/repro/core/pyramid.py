"""Circle counts from the mip pyramid — the paper's "zoom" made shape-static.

The paper counts points inside a circle of radius r by scanning all pixels in
the circle (cost O(r^2), unbounded).  TPU adaptation (DESIGN.md §2): pick the
pyramid level l where the circle's diameter fits a fixed T x T tile
(2r + 1 <= T * 2**l), gather ONE (T, T, C) tile around the query, apply the
circular mask against cell centers, and sum.  Cost is O(T^2 * C) regardless of
r and N — level selection IS the zoom.

Level 0 reproduces the paper exactly (pixel centers within r); coarser levels
approximate the circle with 2**l-pixel cells, which only matters transiently
inside the radius loop (the final count/classify can be re-done at level 0
when the radius permits).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.grid import GridConfig, GridIndex


def level_for_radius(r: jax.Array, cfg: GridConfig) -> jax.Array:
    """Smallest level whose T-cell window FULLY contains the circle.

    Worst case (query at a cell edge) the window covers (T/2 - 1.5) level
    cells of radius, so we need 2**l >= 2r / (T - 3).  Guarantees the masked
    window count equals the full circle count (tests + kernel contract).
    GridConfig rejects tile <= 3, so the (T - 3) margin is always positive
    here."""
    need = 2.0 * r.astype(jnp.float32) / jnp.float32(cfg.tile - 3)
    l = jnp.ceil(jnp.log2(jnp.maximum(need, 1.0))).astype(jnp.int32)
    return jnp.clip(l, 0, cfg.levels - 1)


def _count_at_level(
    arr: jax.Array, level: int, q: jax.Array, r: jax.Array, cfg: GridConfig
) -> jax.Array:
    """Masked circle count from one pyramid level.  arr: (S, S, C) int32."""
    t = cfg.tile
    s = arr.shape[0]
    scale = 1 << level
    qx, qy = q[0], q[1]
    cx = jnp.floor(qx / scale).astype(jnp.int32)
    cy = jnp.floor(qy / scale).astype(jnp.int32)
    ox = jnp.clip(cx - t // 2, 0, s - t)
    oy = jnp.clip(cy - t // 2, 0, s - t)
    tile = lax.dynamic_slice(arr, (ox, oy, 0), (t, t, arr.shape[-1]))

    # cell centers in base-pixel units
    ci = (ox + jnp.arange(t, dtype=jnp.float32) + 0.5) * scale
    cj = (oy + jnp.arange(t, dtype=jnp.float32) + 0.5) * scale
    rf = r.astype(jnp.float32)
    if cfg.metric == "l1":
        dist = jnp.abs(ci - qx)[:, None] + jnp.abs(cj - qy)[None, :]
        mask = dist <= rf
    else:
        d2 = (ci - qx)[:, None] ** 2 + (cj - qy)[None, :] ** 2
        mask = d2 <= rf * rf
    return jnp.sum(tile * mask[:, :, None].astype(jnp.int32), axis=(0, 1))


def count_in_circle(
    index: GridIndex, cfg: GridConfig, q: jax.Array, r: jax.Array
) -> jax.Array:
    """Per-class counts (C,) of points whose pixel center lies within radius r
    of the continuous grid position q (2,).

    counter="pyramid": one fixed-size tile gather at level l(r) (L2/L1 mask).
    counter="sat": EXACT L-inf (square) count — four gathers, any radius
    (integral.py; beyond-paper variant)."""
    if cfg.counter == "sat":
        from repro.core import integral as integral_lib
        return integral_lib.count_linf(index.sat, q, r)
    level = level_for_radius(r, cfg)
    # every level's (T, T) count, then a select: a `lax.switch` on the level
    # would, under the jnp path's vmap, batch the pyramid it closes over and
    # copy every level once per query (16 GB at grid 4096, 256 queries)
    counts = jnp.stack([
        _count_at_level(arr, lv, q, r, cfg)
        for lv, arr in enumerate(index.pyramid)
    ])
    return counts[level]


def count_total(index: GridIndex, cfg: GridConfig, q: jax.Array, r: jax.Array) -> jax.Array:
    return count_in_circle(index, cfg, q, r).sum()


def seed_radius(
    index: GridIndex, cfg: GridConfig, q: jax.Array, k: int
) -> jax.Array:
    """Per-query Eq.-1 start radius from the pyramid's top levels.

    The coarse pyramid levels are a free local-density sketch: probe the
    circle count at the largest window-contained radius of the top level
    (and of the level below it, whose finer probe wins whenever it already
    sees >= k points), then apply ONE Eq.-1 step to land the start radius
    near the query's own k-neighborhood scale.  Queries whose probes see no
    mass fall back to the global cfg.r0.

    This only changes WHERE the radius loop starts — never what it returns:
    the loop's acceptance band and fallback logic are untouched, so results
    follow whatever radius the schedule converges to.  Shared verbatim by
    the per-query jnp path and (under vmap) the batched pallas path, so the
    seeds are bit-identical across backends by construction.
    """
    r_max = jnp.int32(cfg.max_radius)
    top = cfg.levels - 1
    kf = jnp.float32(k)

    def eq1_step(r_probe, n_probe):
        ratio = jnp.sqrt(kf / jnp.maximum(n_probe, 1).astype(jnp.float32))
        return jnp.round(r_probe.astype(jnp.float32) * ratio).astype(jnp.int32)

    # largest radius whose circle is FULLY contained by the T-cell window at
    # level l (the level_for_radius margin, inverted): r = (T - 3) * 2**l / 2
    r1 = jnp.int32(((cfg.tile - 3) << top) // 2)
    n1 = _count_at_level(index.pyramid[top], top, q, r1, cfg).sum()
    est = eq1_step(r1, n1)
    if top >= 1:
        r2 = jnp.int32(((cfg.tile - 3) << (top - 1)) // 2)
        n2 = _count_at_level(index.pyramid[top - 1], top - 1, q, r2, cfg).sum()
        est = jnp.where(n2 >= k, eq1_step(r2, n2), est)
    return jnp.where(n1 > 0, jnp.clip(est, 1, r_max), jnp.int32(cfg.r0))


def radius_search(
    index: GridIndex, cfg: GridConfig, q: jax.Array, k: int,
    adaptive_r0: bool = False,
) -> dict[str, jax.Array]:
    """The paper's Eq. 1:  r_{t+1} = round(r_t * sqrt(k / n_t)).

    Faithful except for two production guards (DESIGN.md §8): an iteration cap
    (Eq. 1 oscillates on quantized counts) and an acceptance band
    n in [k, ceil(k_slack * k)] (k_slack=1.0 is the paper's exact n == k stop).
    Tracks the smallest radius seen with n >= k as the fallback answer.

    adaptive_r0=True seeds the start radius per query from the pyramid's
    top levels (`seed_radius`) instead of the global cfg.r0.
    """
    k_hi = jnp.int32(max(k, math.ceil(k * cfg.k_slack)))
    r_max = jnp.int32(cfg.max_radius)
    sentinel = r_max + 1

    def cond(state):
        t, _r, done, _best = state
        return jnp.logical_and(t < cfg.max_iters, jnp.logical_not(done))

    def body(state):
        t, r, _done, best = state
        n = count_total(index, cfg, q, r)
        hit = jnp.logical_and(n >= k, n <= k_hi)
        best = jnp.where(n >= k, jnp.minimum(best, r), best)
        # Eq. 1 with integer rounding
        ratio = jnp.sqrt(k / jnp.maximum(n, 1).astype(jnp.float32))
        r_new = jnp.round(r.astype(jnp.float32) * ratio).astype(jnp.int32)
        r_new = jnp.where(n == 0, r * 2, r_new)
        r_new = jnp.clip(r_new, 1, r_max)
        # force progress when rounding stalls
        r_new = jnp.where(
            jnp.logical_and(r_new == r, jnp.logical_not(hit)),
            r + jnp.where(n < k, 1, -1),
            r_new,
        )
        r_next = jnp.where(hit, r, jnp.clip(r_new, 1, r_max))
        return t + 1, r_next, hit, best

    # GridConfig rejects out-of-range r0 eagerly, so no silent clip here
    r0 = seed_radius(index, cfg, q, k) if adaptive_r0 else jnp.int32(cfg.r0)
    t, r, converged, best = lax.while_loop(cond, body, (jnp.int32(0), r0, False, sentinel))

    r_final = jnp.where(converged, r, jnp.where(best <= r_max, best, r_max))
    n_final = count_total(index, cfg, q, r_final)
    return {
        "radius": r_final,
        "count": n_final,
        "iters": t,
        "converged": converged,
    }
