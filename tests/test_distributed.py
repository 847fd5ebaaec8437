"""Multi-device behaviour (8 host devices via subprocess — the main test
process must keep the real 1-device view)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, timeout=420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# The sharded store answers as ONE index over the same points: every
# SearchResult field equal to the pallas backend's, ids (and their labels)
# up to equal distances — the merge orders a tie by global id, one index by
# CSR row.
ONE_INDEX = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro import api
from repro.core import distributed as D
from repro.core.grid import GridConfig
from repro.core.projection import pca_projection


def assert_one_index(got, want, labels, msg):
    for f in ("dists", "valid", "radius", "count", "iters", "converged",
              "truncated"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f"{msg}:{f}")
    d = np.asarray(want.dists)
    gi, wi = np.asarray(got.ids), np.asarray(want.ids)
    for r in range(d.shape[0]):
        fin = np.isfinite(d[r])
        assert (gi[r][~fin] == -1).all() and (wi[r][~fin] == -1).all()
        if not fin.any():
            continue
        inner = fin & (d[r] < d[r][fin].max())
        assert sorted(gi[r][inner]) == sorted(wi[r][inner]), (msg, r)
        assert len(set(gi[r][fin])) == fin.sum(), (msg, r)
        np.testing.assert_array_equal(
            np.asarray(got.labels)[r][fin], labels[gi[r][fin]],
            err_msg=f"{msg}:labels")


def clustered(rng, n, d=8):
    # dense clusters, so window rows overflow row_cap and the global-rank
    # clip decides which records a shard may offer
    centres = rng.normal(size=(6, d)) * 2.0
    pts = centres[rng.integers(0, 6, size=n)] + 0.3 * rng.normal(size=(n, d))
    return (jnp.asarray(pts, jnp.float32),
            jnp.asarray(rng.integers(0, 3, size=n), jnp.int32))


CFG = GridConfig(grid_size=32, tile=8, n_classes=3, window=8, row_cap=8,
                 r0=4, k_slack=2.0)
"""


# Each check below runs in ONE shared subprocess (the interpret-mode
# compiles dominate, and one process shares the single index's programs);
# each test reads its own check's outcome.
CHECKS = {}


def check(name: str, body: str) -> None:
    CHECKS[name] = textwrap.dedent(body)


@pytest.fixture(scope="module")
def one_index() -> dict:
    lines = ["import json, traceback", "results = {}"]
    for name, body in CHECKS.items():
        lines += [
            f"def {name}():",
            textwrap.indent(body, "    "),
            "try:",
            f"    {name}()",
            f"    results[{name!r}] = 'ok'",
            "except Exception:",
            f"    results[{name!r}] = traceback.format_exc()",
        ]
    lines.append("print('RESULTS', json.dumps(results))")
    out = run_py(ONE_INDEX + "\n".join(lines), timeout=900)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


for _n in (2, 4, 8):
    check(f"matches_one_index_{_n}", f"""
        n_shards = {_n}
        rng = np.random.default_rng(n_shards)
        pts, labels = clustered(rng, 3000)
        proj = pca_projection(pts)
        q = jnp.asarray(np.asarray(pts[:12]) + 0.1 * rng.normal(size=(12, 8)),
                        jnp.float32)
        one = api.ActiveSearcher.build(
            pts, labels=labels, cfg=CFG, proj=proj,
            plan=api.ExecutionPlan(backend="pallas"))
        if n_shards == len(jax.devices()):
            sh = api.ActiveSearcher.build(
                pts, labels=labels, cfg=CFG, proj=proj,
                plan=api.ExecutionPlan(backend="sharded"))
        else:
            mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("s",))
            sh = api.ActiveSearcher.build_sharded(
                pts, mesh=mesh, axis="s", labels=labels, cfg=CFG, proj=proj)
        st = sh.stats()
        assert st["n_shards"] == n_shards and st["n_points"] == 3000
        assert sum(st["shard_points"]) == 3000
        for mode in ("refined", "paper"):
            want = one.search(q, 6, mode=mode)
            assert np.asarray(want.truncated).any()
            assert_one_index(sh.search(q, 6, mode=mode), want,
                             np.asarray(labels), mode)
    """)


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_sharded_matches_one_index(one_index, n_shards):
    """2, 4 and 8 shards answer as one pallas index over the same points,
    in refined and paper modes; 8 shards (every local device) through
    `ActiveSearcher.build(plan=ExecutionPlan(backend="sharded"))`."""
    assert one_index[f"matches_one_index_{n_shards}"] == "ok", \
        one_index[f"matches_one_index_{n_shards}"]


check("row_cap_overflow", """
    from repro.core import batched
    from repro.core.active_search import window_spans

    rng = np.random.default_rng(7)
    # one tight blob, far from two anchors: a few window rows hold
    # dozens of records over cells owned by different shards
    blob = rng.normal(size=(300, 2)) * 0.05
    pts = jnp.asarray(np.concatenate([blob, [[-4.0, -4.0], [4.0, 4.0]]]),
                      jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, size=302), jnp.int32)
    cfg = GridConfig(grid_size=64, tile=8, n_classes=3, window=8,
                     row_cap=8, r0=4, k_slack=2.0)
    proj = pca_projection(pts)
    q = jnp.asarray(blob[:8] + 0.01, jnp.float32)
    one = api.ActiveSearcher.build(
        pts, labels=labels, cfg=cfg, proj=proj,
        plan=api.ExecutionPlan(backend="pallas"))
    q_grid = batched._project(one.index, cfg, q)
    start, end = window_spans(one.index, cfg, q_grid)
    assert int(np.max(np.asarray(end - start))) > 3 * cfg.row_cap
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("s",))
    sh = api.ActiveSearcher.build_sharded(
        pts, mesh=mesh, axis="s", labels=labels, cfg=cfg, proj=proj)
    for mode in ("refined", "paper"):
        assert_one_index(sh.search(q, 8, mode=mode),
                         one.search(q, 8, mode=mode),
                         np.asarray(labels), mode)
""")


def test_sharded_row_cap_overflow_takes_one_index_rows(one_index):
    """One window row holds more than row_cap records: one index keeps the
    first row_cap in global CSR order, and each shard offers exactly its
    records among them (a shard's own first row_cap would differ)."""
    assert one_index["row_cap_overflow"] == "ok", one_index["row_cap_overflow"]


check("classify", """
    rng = np.random.default_rng(11)
    pts, labels = clustered(rng, 2000)
    proj = pca_projection(pts)
    q = jnp.asarray(rng.normal(size=(16, 8)) * 2.0, jnp.float32)
    one = api.ActiveSearcher.build(
        pts, labels=labels, cfg=CFG, proj=proj,
        plan=api.ExecutionPlan(backend="pallas"))
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("s",))
    sh = api.ActiveSearcher.build_sharded(
        pts, mesh=mesh, axis="s", labels=labels, cfg=CFG, proj=proj)
    for mode in ("refined", "paper"):
        np.testing.assert_array_equal(
            np.asarray(sh.classify(q, 6, mode=mode)),
            np.asarray(one.classify(q, 6, mode=mode)), err_msg=mode)
""")


def test_sharded_classify_matches_one_index(one_index):
    """classify on 4 shards gives one index's predictions: the majority
    vote, the count fallback on short or truncated lanes, and mode="paper"
    (the count argmax at the final radius)."""
    assert one_index["classify"] == "ok", one_index["classify"]


check("batcher", """
    from repro.launch.serve import DynamicBatcher

    rng = np.random.default_rng(3)
    pts, labels = clustered(rng, 2000)
    plan = api.ExecutionPlan(backend="sharded", chunk_size=8)
    sh = api.ActiveSearcher.build(pts, labels=labels, cfg=CFG,
                                  proj=pca_projection(pts), plan=plan)
    assert sh.mesh.size == len(jax.devices())
    q = np.asarray(rng.normal(size=(13, 8)) * 2.0, np.float32)
    b = DynamicBatcher(sh, k=6, max_batch=16)
    futs = [b.submit(q[:5]), b.submit(q[5:])]
    cls = b.submit(q, op="classify")
    b.drain()
    want = sh.search(jnp.asarray(q), 6)
    for f in want._fields:
        got = np.concatenate([getattr(fu.result(), f) for fu in futs])
        np.testing.assert_array_equal(got, np.asarray(getattr(want, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(
        cls.result(), np.asarray(sh.classify(jnp.asarray(q), 6)))
""")


def test_sharded_build_served_through_batcher(one_index):
    """`build(plan=sharded)` served through DynamicBatcher (queries put
    uncommitted on one device, padded, chunked) equals direct calls."""
    assert one_index["batcher"] == "ok", one_index["batcher"]


check("matches_single", """
    from repro.core.grid import build_index
    from repro.core.projection import identity_projection

    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.normal(size=(4096, 2)), jnp.float32)
    cfg = GridConfig(grid_size=128, tile=16, window=48, row_cap=48, r0=6,
                     k_slack=2.0)
    proj = identity_projection(pts)
    sharded = D.build_sharded_index(pts, cfg, proj, mesh, "data")
    q = jnp.asarray(rng.normal(size=(16, 2)), jnp.float32)
    res = D.sharded_search(sharded, cfg, q, 8, mesh, "data")
    one = api.ActiveSearcher.from_index(
        build_index(pts, cfg, proj), cfg,
        plan=api.ExecutionPlan(backend="pallas"))
    assert_one_index(res, one.search(q, 8), np.zeros(4096, np.int32),
                     "refined")
""")


def test_sharded_index_matches_single(one_index):
    """distributed.sharded_search over 8 shards equals one pallas index
    over the same points."""
    assert one_index["matches_single"] == "ok", one_index["matches_single"]


def test_sharded_backend_via_facade():
    """ActiveSearcher.build_sharded registers mesh+axis on the handle and the
    "sharded" backend merges per-shard searchers; results match the direct
    distributed.sharded_search call bit-for-bit."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro import api
        from repro.core import distributed as D
        from repro.core.grid import GridConfig
        from repro.core.projection import identity_projection

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
        rng = np.random.default_rng(0)
        pts = jnp.asarray(rng.normal(size=(4096, 2)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 3, size=4096), jnp.int32)
        cfg = GridConfig(grid_size=128, tile=16, n_classes=3, window=48,
                         row_cap=48, r0=6, k_slack=2.0)
        proj = identity_projection(pts)
        s = api.ActiveSearcher.build_sharded(
            pts, mesh=mesh, axis="data", labels=labels, cfg=cfg, proj=proj)
        assert s.plan.backend == "sharded"
        q = D.replicate_queries(
            jnp.asarray(rng.normal(size=(16, 2)), jnp.float32), mesh)
        res = s.search(q, 8)
        want = D.sharded_search(s.index, cfg, q, 8, mesh, "data")
        for f in res._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, f)), np.asarray(getattr(want, f)),
                err_msg=f)
        preds = s.classify(q, 8)
        assert preds.shape == (16,)
        assert int(np.asarray(preds).min()) >= 0
        print("sharded facade ok")
    """)


def test_train_step_on_2x4_mesh():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.launch.mesh import make_host_mesh
        from repro.launch import steps as st
        from repro.optim import adamw

        cfg = get_smoke("internlm2-1.8b")
        mesh = make_host_mesh(2, 4)
        opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0)
        _, state_abs, state_sh, jit_for = st.make_train_step(
            cfg, opt_cfg, mesh, st.StepConfig(accum=2))
        state = st.init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg,
                                    st.StepConfig(accum=2), mesh)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)), jnp.int32),
        }
        babs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch)
        with mesh:
            fn = jit_for(babs)
            losses = []
            for _ in range(3):
                state, m = fn(state, batch)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        print("losses", losses)
    """)


def test_elastic_checkpoint_across_meshes(tmp_path):
    run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.launch.mesh import make_host_mesh
        from repro.launch import steps as st
        from repro.optim import adamw
        from repro.checkpoint.store import CheckpointManager

        cfg = get_smoke("internlm2-1.8b")
        sc = st.StepConfig()
        opt_cfg = adamw.AdamWConfig()
        mesh_a = make_host_mesh(2, 4)
        state = st.init_train_state(jax.random.PRNGKey(1), cfg, opt_cfg, sc, mesh_a)
        mgr = CheckpointManager({str(tmp_path)!r})
        mgr.save(1, state, blocking=True)

        mesh_b = make_host_mesh(4, 2)          # DIFFERENT mesh
        abstract = st.train_state_shapes(cfg, opt_cfg, sc)
        sh_b = st._ns(mesh_b, st.train_state_specs(abstract, cfg, mesh_b))
        restored = mgr.restore(1, abstract, shardings=sh_b)
        a = np.asarray(jax.device_get(state["params"]["embed"]))
        b = np.asarray(jax.device_get(restored["params"]["embed"]))
        np.testing.assert_array_equal(a, b)
        print("elastic OK")
    """)


def test_compressed_psum_shard_map():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim.compression import compressed_psum

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("dp",))
        g = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)), jnp.float32)
        err = jnp.zeros((8, 64), jnp.float32)

        def f(g, e):
            out, new_e = compressed_psum(g[0], e[0], "dp")
            return out[None], new_e[None]

        fn = jax.shard_map(f, mesh=mesh, in_specs=(P("dp"), P("dp")),
                           out_specs=(P("dp"), P("dp")), check_vma=False)
        mean_hat, err2 = fn(g, err)
        true_mean = np.asarray(g).mean(axis=0)
        got = np.asarray(mean_hat[0])
        scale = np.abs(np.asarray(g)).max() / 127
        np.testing.assert_allclose(got, true_mean, atol=8 * scale)
        print("compressed psum OK")
    """)
