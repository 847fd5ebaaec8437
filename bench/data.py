"""Inputs made from the seed: the stored points, the query pool and the
projection to the grid plane.

Points and queries are drawn on the device, in one jitted call, from a
Gaussian mixture (`generator` in the configuration file) and mapped to small
integers stored as float32, as SIFT descriptors are.  The set is fixed by the
configuration's `data_seed`; the run's seed orders it (`make_data`).  With integer values
every float32 sum the system computes over them is exact: squared l2
distances, and the projection below.  That is what lets the plain reference
(`reference.py`) reproduce the grid, the radius loop and the candidate
ranking bit for bit, without taking anything from the program.

The projection is the top two principal directions of the points, put on a
fixed-point grid: integer weights (|w| <= 255, so bfloat16-exact) over 256,
and per-axis extents whose width is a power of two.  Then the program's
`(x @ M - lo) / (hi - lo) * grid_size` is exact in float32 at any matmul
precision, and the reference computes the same coordinates.
"""

from __future__ import annotations

import math

import numpy as np

WEIGHT_MAX = 255     # bfloat16 holds integers up to 256 exactly
WEIGHT_DENOM = 256   # projection weights are multiples of 1/256
FILL = 0.9           # share of the power-of-two extent the data spans
CHUNK_ROWS = 65_536  # rows drawn per step of the generator


def key_from_seed(seed: int):
    """A PRNG key from any non-negative seed, 32 bits at a time."""
    import jax

    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _n_chunks(n: int) -> int:
    """The divisor of n nearest above n / CHUNK_ROWS (equal-size chunks)."""
    want = max(1, math.ceil(n / CHUNK_ROWS))
    for c in range(want, n + 1):
        if n % c == 0:
            return c
    return n


def make_data(conf: dict, seed: int):
    """(points (N, d), queries (Q, d)) float32 on the default device.

    The data set is the configuration's: point i and query j are fixed
    functions of the generator's `data_seed` and of i or j.  The run's seed
    only orders them (a permutation of the points, which sets their ids and
    their order inside each grid cell, and one of the query pool), so every
    seed stores and asks the same set, in another order."""
    import jax
    import jax.numpy as jnp

    gen = conf["generator"]
    n, d, nq = conf["n_points"], conf["dim"], conf["n_queries"]
    n_c, std = gen["n_clusters"], gen["cluster_std"]
    scale, offset, vmax = gen["scale"], gen["offset"], conf["value_max"]
    chunks = _n_chunks(n)

    def rows(key, centers, ids):
        def one(i):
            ka, kb = jax.random.split(jax.random.fold_in(key, i))
            lab = jax.random.randint(ka, (), 0, n_c)
            return centers[lab] + std * jax.random.normal(kb, (d,))

        v = jax.vmap(one)(ids)
        return jnp.clip(jnp.round(offset + scale * v), 0, vmax)

    @jax.jit
    def draw(data_key, order_key):
        kc, kp, kq = jax.random.split(data_key, 3)
        kpo, kqo = jax.random.split(order_key)
        centers = jax.random.normal(kc, (n_c, d), jnp.float32)
        perm = jax.random.permutation(kpo, n).reshape(chunks, n // chunks)
        pts = jax.lax.map(lambda ids: rows(kp, centers, ids), perm)
        qs = rows(kq, centers, jax.random.permutation(kqo, nq))
        return pts.reshape(n, d), qs

    return jax.block_until_ready(
        draw(key_from_seed(gen["data_seed"]), key_from_seed(seed)))


def _project(x, w):
    """x @ w at HIGHEST precision (exact for the integer inputs used here)."""
    import jax
    import jax.numpy as jnp

    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _principal_axes(points) -> np.ndarray:
    """(d, 2) top principal directions, float64, sign fixed by the entry of
    largest magnitude."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def moments(x):
        n = x.shape[0]
        mu = jnp.mean(x, axis=0)
        xtx = jnp.matmul(x.T, x, precision=jax.lax.Precision.HIGHEST) / n
        return mu, xtx

    mu, xtx = (np.asarray(a, np.float64) for a in moments(points))
    cov = xtx - np.outer(mu, mu)
    _, vecs = np.linalg.eigh(cov)
    axes = vecs[:, ::-1][:, :2].copy()
    for j in range(2):
        if axes[np.argmax(np.abs(axes[:, j])), j] < 0:
            axes[:, j] = -axes[:, j]
    return axes


def fixed_point_projection(points, queries, value_max: int) -> dict:
    """The exact projection: integer weights `w` (d, 2) over `denom`, and
    extents `lo` and `span` (powers of two) per axis.  Coordinates are
    (x @ (w / denom) - lo) / span * grid_size."""
    import jax.numpy as jnp

    axes = _principal_axes(points)
    d = axes.shape[0]
    w = np.zeros((d, 2), np.int64)
    lo = np.zeros(2)
    span = np.zeros(2)
    for j in range(2):
        u = axes[:, j]
        s_max = WEIGHT_MAX / np.max(np.abs(u))
        g = [np.asarray(_project(a, jnp.asarray(u[:, None], jnp.float32)))
             for a in (points, queries)]
        r_unit = max(float(x.max()) for x in g) - min(float(x.min()) for x in g)
        # the largest power of two the range can fill to FILL at s_max
        e = math.floor(math.log2(s_max * r_unit / WEIGHT_DENOM / FILL))
        while True:
            s = FILL * 2.0 ** e * WEIGHT_DENOM / r_unit
            wj = np.round(u * s).astype(np.int64)
            # every partial sum of x @ wj is an integer below 2**24
            if value_max * np.abs(wj).sum() < 2 ** 24:
                break
            e -= 1
        wf = jnp.asarray(wj[:, None] / WEIGHT_DENOM, jnp.float32)
        g = [np.asarray(_project(a, wf), np.float64)[:, 0]
             for a in (points, queries)]
        gmin = min(x.min() for x in g)
        gmax = max(x.max() for x in g)
        width = 2.0 ** e
        start = math.floor((gmin - (width - (gmax - gmin)) / 2)
                           * WEIGHT_DENOM) / WEIGHT_DENOM
        # strictly inside [lo, lo + span * (1 - 2**-20)): the program clips
        # coordinates at grid_size - 1e-3
        if not (gmin >= start and gmax < start + width * (1 - 2.0 ** -20)):
            raise ValueError(f"projection axis {j}: range [{gmin}, {gmax}] "
                             f"does not fit the extent {width} at {start}")
        w[:, j], lo[j], span[j] = wj, start, width
    return {"w": w, "denom": WEIGHT_DENOM, "lo": lo, "span": span}


def program_projection(proj: dict):
    """The projection as the program takes it (`repro.api.Projection`)."""
    import jax.numpy as jnp

    from repro import api

    return api.Projection(
        matrix=jnp.asarray(proj["w"] / proj["denom"], jnp.float32),
        lo=jnp.asarray(proj["lo"], jnp.float32),
        hi=jnp.asarray(proj["lo"] + proj["span"], jnp.float32),
    )
