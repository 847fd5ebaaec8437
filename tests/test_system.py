"""End-to-end system behaviour: the paper's pipeline + the LM integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import active_search as act, exact
from repro.core.grid import GridConfig, build_index
from repro.core.projection import identity_projection, pca_projection
from repro.launch.mesh import make_host_mesh
from repro.launch.lm_serve import Engine, ServeConfig, build_datastore_from_model
from repro.core import knn_lm
from repro.models import model as M

pytestmark = pytest.mark.slow  # full model/system drills; fast tier skips

def test_paper_pipeline_accuracy(rng):
    """The paper's §3 setup at reduced scale: random 2-D points, 3 classes,
    k=11; active-search classification vs exact-kNN ground truth >= 90%."""
    pts = jnp.asarray(rng.normal(size=(4000, 2)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, size=4000), jnp.int32)
    cfg = GridConfig(grid_size=512, tile=16, n_classes=3, window=48,
                     row_cap=48, r0=20, k_slack=2.0)
    idx = build_index(pts, cfg, identity_projection(pts), labels=labels)
    q = jnp.asarray(rng.normal(size=(100, 2)), jnp.float32)
    pred = act.classify(idx, cfg, q, 11)
    truth = exact.classify(q, pts, labels, 11, n_classes=3)
    acc = float(jnp.mean((pred == truth).astype(jnp.float32)))
    assert acc >= 0.9, acc


def test_high_dim_via_projection(rng):
    """Beyond-paper: 64-dim keys through a PCA projection + re-rank."""
    base = rng.normal(size=(3000, 8))
    lift = rng.normal(size=(8, 64)) * 0.5
    pts = jnp.asarray(base @ lift + rng.normal(size=(3000, 64)) * 0.05, jnp.float32)
    cfg = GridConfig(grid_size=256, tile=16, window=64, row_cap=64, r0=6,
                     k_slack=4.0)
    idx = build_index(pts, cfg, pca_projection(pts))
    q = pts[:32] + 0.01
    res = act.search(idx, cfg, q, 5)
    ex = exact.knn(q, pts, 5)
    recall = np.mean([
        len(set(np.asarray(res.ids[i]).tolist())
            & set(np.asarray(ex.ids[i]).tolist())) / 5
        for i in range(32)
    ])
    assert recall > 0.5, recall  # projection is lossy; re-rank keeps it useful


def test_serve_engine_with_knn_head(rng):
    cfg = get_smoke("internlm2-1.8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh(1, 1)
    knn_cfg = knn_lm.KNNLMConfig(k=4)
    corpus = rng.integers(0, cfg.vocab_size, size=(8, 33), dtype=np.int32)
    store = build_datastore_from_model(cfg, params, corpus, knn_cfg)
    engine = Engine(cfg, params, mesh, ServeConfig(knn=knn_cfg, max_new_tokens=4),
                    datastore=store)
    prompts = rng.integers(0, cfg.vocab_size, size=(2, 8), dtype=np.int32)
    toks, _ = engine.generate(prompts)
    assert toks.shape == (2, 4)
    assert (toks >= 0).all() and (toks < cfg.vocab_size).all()


def test_serve_greedy_deterministic(rng):
    cfg = get_smoke("internlm2-1.8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh(1, 1)
    engine = Engine(cfg, params, mesh, ServeConfig(max_new_tokens=4))
    prompts = rng.integers(0, cfg.vocab_size, size=(2, 8), dtype=np.int32)
    t1, _ = engine.generate(prompts)
    engine2 = Engine(cfg, params, mesh, ServeConfig(max_new_tokens=4))
    t2, _ = engine2.generate(prompts)
    np.testing.assert_array_equal(t1, t2)


def test_retrieved_decode_close_to_full(rng):
    """Retrieval-memory decode == full decode when retrieval covers the whole
    cache (w + m >= T)."""
    cfg = get_smoke("internlm2-1.8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    b, s = 1, 12
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}
    _, caches, _ = M.prefill(params, cfg, batch, cache_len=s + 2)
    tok = jnp.asarray([3], jnp.int32)
    full_logits, _, _ = M.decode_step(params, cfg, caches, tok, jnp.int32(s))
    # local window covers [s-3, s]; retrieval covers the disjoint rest [0, s-4]
    w = 4
    retrieved = jnp.arange(s - w + 1, dtype=jnp.int32)[None, :]
    ok = jnp.ones_like(retrieved, dtype=bool)
    r_logits, _, _ = M.decode_step(
        params, cfg, caches, tok, jnp.int32(s),
        retrieved=(retrieved, ok, w),
    )
    np.testing.assert_allclose(
        np.asarray(full_logits.astype(jnp.float32)),
        np.asarray(r_logits.astype(jnp.float32)), atol=0.1,
    )
