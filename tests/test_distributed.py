"""Multi-device behaviour (8 host devices via subprocess — the main test
process must keep the real 1-device view)."""

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


def test_sharded_index_matches_single():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core import distributed as D
        from repro.core import active_search as act, exact
        from repro.core.grid import GridConfig, build_index
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
        ex = exact.knn(q, pts, 8)
        recall = np.mean([
            len(set(np.asarray(res.ids[i]).tolist())
                & set(np.asarray(ex.ids[i]).tolist())) / 8
            for i in range(16)
        ])
        assert recall > 0.85, recall
        print("recall", recall)
    """)


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
