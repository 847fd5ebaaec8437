"""Head/vocab padding invariants: pad rows are dead weight — garbage in the
pad slots must not change any output, and pads never win argmax."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import model as M
from repro.models.config import ParallelismPolicy


def _padded_cfg():
    base = get_smoke("internlm2-1.8b")   # 8 heads, kv 4, vocab 512
    return dataclasses.replace(
        base,
        policy=dataclasses.replace(
            base.policy, pad_heads_to=12, pad_kv_heads_to=6, pad_vocab_to=520
        ),
    )


def _poison_pads(params, cfg):
    """Overwrite pad-head / pad-vocab parameter rows with large garbage."""
    p = jax.tree.map(lambda a: a, params)  # shallow copy
    for blk in p["blocks"]:
        core = blk["core"]
        # stacked leading (R,) axis: wq (R, d, hq_eff, hd), wo (R, hq_eff, hd, d)
        core["wq"] = core["wq"].at[..., cfg.n_heads:, :].set(37.0)
        core["wo"] = core["wo"].at[:, cfg.n_heads:].set(37.0)
    p["embed"] = p["embed"].at[cfg.vocab_size:, :].set(37.0)
    p["lm_head"] = p["lm_head"].at[:, cfg.vocab_size:].set(37.0)
    return p


def test_pad_slots_do_not_affect_outputs(rng, key):
    cfg = _padded_cfg()
    params = M.init_params(key, cfg)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32),
    }
    logits1, _ = M.forward(params, cfg, batch)
    logits2, _ = M.forward(_poison_pads(params, cfg), cfg, batch)
    np.testing.assert_allclose(
        np.asarray(logits1[..., : cfg.vocab_size].astype(jnp.float32)),
        np.asarray(logits2[..., : cfg.vocab_size].astype(jnp.float32)),
        atol=1e-3,
    )


def test_pad_vocab_never_wins_argmax(rng, key):
    cfg = _padded_cfg()
    params = M.init_params(key, cfg)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)}
    logits, _, _ = M.prefill(params, cfg, batch, cache_len=18)
    assert logits.shape[-1] == cfg.vocab_eff == 520
    assert int(jnp.argmax(logits, -1).max()) < cfg.vocab_size


def test_pad_heads_get_zero_gradient(rng, key):
    cfg = _padded_cfg()
    params = M.init_params(key, cfg)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32),
    }
    grads = jax.grad(lambda p: M.loss_fn(p, cfg, batch)[0])(params)
    for blk in grads["blocks"]:
        gq = np.asarray(blk["core"]["wq"])           # (R, d, hq_eff, hd)
        assert np.abs(gq[..., cfg.n_heads:, :]).max() == 0.0
        go = np.asarray(blk["core"]["wo"])           # (R, hq_eff, hd, d)
        assert np.abs(go[:, cfg.n_heads:]).max() == 0.0
    ge = np.asarray(grads["embed"])
    assert np.abs(ge[cfg.vocab_size:]).max() == 0.0


def test_padded_train_loss_finite_and_decreasing(rng, key):
    from repro.optim import adamw
    cfg = _padded_cfg()
    params = M.init_params(key, cfg)
    opt = adamw.init(params)
    ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32),
    }

    @jax.jit
    def step(params, opt):
        (loss, _), g = jax.value_and_grad(M.loss_fn, has_aux=True)(params, cfg, batch)
        params, opt, _ = adamw.update(ocfg, g, opt, params)
        return params, opt, loss

    losses = []
    for _ in range(3):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


# ------------------------------------------------ dynamic batching queue -----
# The serving tier's pow2 padding (launch/serve.py DynamicBatcher) obeys the
# same invariant as the head/vocab pads above: pad rows are dead weight.
# Queue-padded search/classify results must be bit-identical to unpadded
# single-request calls for every ragged size, and pads never leak into
# results or the queue's truncation stats.

from repro import api  # noqa: E402
from repro.core.grid import GridConfig, build_index  # noqa: E402
from repro.core.projection import identity_projection  # noqa: E402
from repro.launch.serve import DynamicBatcher, _pow2  # noqa: E402

QCFG = GridConfig(grid_size=64, tile=8, n_classes=3, window=16, row_cap=8,
                  r0=4, k_slack=2.0)


def _searcher(rng, n=512):
    pts = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, size=n), jnp.int32)
    return api.ActiveSearcher.from_index(
        build_index(pts, QCFG, identity_projection(pts), labels=labels), QCFG
    )


def test_queue_padded_search_bit_identical_ragged_sizes(rng):
    """Every ragged request size 1..B round-trips the queue bit-identically
    to a direct unpadded search — ids, dists, AND the truncated/Eq.-1 stat
    fields, each sliced to exactly the submitted rows."""
    s = _searcher(rng)
    for n in range(1, 10):  # crosses the 1/2/4/8/16 pow2 boundaries
        queries = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
        q = DynamicBatcher(s, k=5)
        fut = q.submit(queries)
        q.drain()
        got, want = fut.result(timeout=0), s.search(queries, 5)
        for f in api.SearchResult._fields:
            a = getattr(got, f)
            assert type(a) is np.ndarray, f"{f}: {type(a)} is not on the host"
            assert a.shape[0] == n, f"{f}: pad leaked into shape {a.shape}"
            np.testing.assert_array_equal(
                a, np.asarray(getattr(want, f)), err_msg=f"n={n}:{f}")
        assert q.stats["pad_rows"] == _pow2(n) - n


def test_queue_padded_classify_bit_identical_ragged_sizes(rng):
    s = _searcher(rng)
    for n in (1, 3, 5, 8):
        queries = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
        q = DynamicBatcher(s, k=5)
        fut = q.submit(queries, op="classify")
        q.drain()
        got = fut.result(timeout=0)
        assert type(got) is np.ndarray and got.shape == (n,)
        np.testing.assert_array_equal(
            got, np.asarray(s.classify(queries, 5)), err_msg=f"n={n}")


def test_queue_coalesces_and_slices_per_request(rng):
    """Several ragged requests coalesce into ONE padded batch; each future
    resolves to exactly its own rows."""
    s = _searcher(rng)
    q = DynamicBatcher(s, k=5, max_batch=64)
    sizes = (1, 3, 5, 2)
    queries = [jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
               for n in sizes]
    futs = [q.submit(x) for x in queries]
    q.drain()
    assert q.stats["batches"] == 1
    assert q.stats["pad_rows"] == _pow2(sum(sizes)) - sum(sizes)
    for x, fut in zip(queries, futs):
        got, want = fut.result(timeout=0), s.search(x, 5)
        for f in api.SearchResult._fields:
            a = getattr(got, f)
            assert type(a) is np.ndarray and a.shape[0] == x.shape[0], f
            np.testing.assert_array_equal(
                a, np.asarray(getattr(want, f)), err_msg=f)


@pytest.mark.parametrize("backend", ["jnp", "pallas", "exact"])
def test_queue_host_answers_match_direct_calls_per_backend(rng, backend):
    """Ragged requests coalesced into one padded batch (8 rows, then 17 ->
    32: across the pow2 boundaries) resolve to host arrays holding exactly
    each request's rows, bit-equal to a direct unpadded search or classify
    of those rows."""
    s = _searcher(rng).with_plan(backend=backend)
    for sizes in ((1, 2, 5), (2, 5, 10)):
        queries = [np.asarray(rng.normal(size=(n, 2)), np.float32)
                   for n in sizes]
        q = DynamicBatcher(s, k=5)
        futs = [q.submit(x) for x in queries]
        cls = [q.submit(x, op="classify") for x in queries]
        q.drain()
        assert q.stats["batches"] == 2
        for x, fut, cf in zip(queries, futs, cls):
            got, want = fut.result(timeout=0), s.search(jnp.asarray(x), 5)
            for f in api.SearchResult._fields:
                a = getattr(got, f)
                assert type(a) is np.ndarray, f"{backend}:{f}: {type(a)}"
                np.testing.assert_array_equal(
                    a, np.asarray(getattr(want, f)),
                    err_msg=f"{backend}:{sizes}:{f}")
            c = cf.result(timeout=0)
            assert type(c) is np.ndarray and c.shape == (x.shape[0],)
            np.testing.assert_array_equal(
                c, np.asarray(s.classify(jnp.asarray(x), 5)),
                err_msg=f"{backend}:{sizes}:classify")


def test_queue_pads_never_inflate_truncation_stats(rng):
    """The queue's truncated_rows counter matches the direct search's count
    over the REAL rows — replicated pad rows (which truncate whenever the
    last real row does) are excluded."""
    rng2 = np.random.default_rng(7)
    # clustered points overflow row_cap=8 buckets -> real truncation
    pts = jnp.asarray(rng2.normal(size=(512, 2)) * 0.05, jnp.float32)
    s = api.ActiveSearcher.from_index(
        build_index(pts, QCFG, identity_projection(pts)), QCFG)
    queries = jnp.asarray(rng2.normal(size=(5, 2)) * 0.05, jnp.float32)
    direct = int(np.asarray(s.search(queries, 5).truncated).sum())
    assert direct > 0, "fixture should truncate"
    q = DynamicBatcher(s, k=5)
    q.submit(queries)
    q.drain()
    assert q.stats["pad_rows"] == 3
    assert q.stats["truncated_rows"] == direct


def test_queue_inserts_drain_between_search_batches(rng):
    """A queued insert is invisible to the search batch already in flight
    and visible to the next one — the backlog drains on the batch boundary
    with the counters tracking it."""
    s = _searcher(rng)
    queries = jnp.asarray(rng.normal(size=(4, 2)), jnp.float32)
    new_pts = jnp.asarray(rng.normal(size=(32, 2)), jnp.float32)
    new_labels = jnp.asarray(rng.integers(0, 3, size=32), jnp.int32)

    q = DynamicBatcher(s, k=5)
    f1 = q.submit(queries)
    assert q.offer_insert(new_pts, labels=new_labels) == 32
    assert q.stats["insert_backlog"] == 32
    assert q.step()  # serves the search batch FIRST (insert still queued)
    assert q.stats["insert_backlog"] == 32
    f2_before = s.search(queries, 5)
    for f in api.SearchResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(f1.result(timeout=0), f)),
            np.asarray(getattr(f2_before, f)), err_msg=f"pre-insert:{f}")

    assert q.step()  # drains the backlog between batches
    assert q.stats["insert_backlog"] == 0
    assert q.stats["inserts_applied"] == 32
    assert q.stats["insert_backlog_peak"] == 32

    f2 = q.submit(queries)
    q.drain()
    grown = s.insert(new_pts, labels=new_labels)
    for f in api.SearchResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(f2.result(timeout=0), f)),
            np.asarray(getattr(grown.search(queries, 5), f)),
            err_msg=f"post-insert:{f}")
