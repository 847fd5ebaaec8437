"""Batched generation with the paper's technique as a first-class feature:
a kNN-LM head whose datastore is searched with ACTIVE SEARCH (core/knn_lm).

Flow per batch of requests:
  prefill(prompts) -> caches + last hidden
  loop: decode_step -> hidden h_t
        active-search h_t in the datastore -> p_knn   (cost independent of N)
        logits' = log( lam * p_knn + (1-lam) * p_lm )
        sample/argmax -> next token

The datastore maps hidden states -> observed next tokens (Khandelwal-style);
build_datastore_from_model() harvests it from the model's own prefill pass
over a corpus.  Online datastore growth goes through the index's serving
queue (`launch/serve.DynamicBatcher`).

  python -m repro.launch.lm_serve --arch internlm2-1.8b --knn
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs import ARCH_NAMES, get_smoke
from repro.core import knn_lm
from repro.core.grid import GridIndex
from repro.launch.mesh import make_host_mesh
from repro.launch import steps as st
from repro.launch.serve import DynamicBatcher
from repro.models import model as M
from repro.utils.compile_cache import enable_compile_cache


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    greedy: bool = True
    temperature: float = 1.0
    knn: knn_lm.KNNLMConfig | None = None
    seed: int = 0



class Engine:
    """Batched generation over a fixed mesh; caches donated step to step."""

    def __init__(self, cfg, params, mesh, sc: ServeConfig,
                 datastore: GridIndex | None = None):
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.sc = sc
        self.datastore = datastore
        self._serve_step, _, self._params_sh, self._jit_for = st.make_serve_step(
            cfg, mesh
        )
        self._compiled = {}
        # --knn-online growth queue: opened on first use and kept across
        # batches, so chained inserts reuse the searcher's slack state (free
        # bucket slots) instead of re-deriving the layout every time
        self._ds_queue: DynamicBatcher | None = None
        self.stats = {"prefill_s": 0.0, "decode_s": 0.0, "tokens": 0}

    def _decode_fn(self, caches, token, pos):
        key = tuple(jax.tree.leaves(jax.tree.map(lambda a: a.shape, caches))[0:1])
        if key not in self._compiled:
            dec_abs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                {"caches": caches, "token": token, "pos": pos},
            )
            with self.mesh:
                self._compiled[key] = self._jit_for(dec_abs)
        return self._compiled[key]

    def generate(self, prompts: np.ndarray, max_new: int | None = None):
        """prompts: (B, S) int32.  Returns (tokens (B, new), hiddens) where
        hiddens is a LIST of new-1 per-step (B, d) arrays — hiddens[j] is the
        state that predicted tokens[:, j+1] (the prefill hidden that produced
        tokens[:, 0] is not collected), the pairing extend_datastore relies
        on."""
        sc = self.sc
        max_new = max_new or sc.max_new_tokens
        b, s = prompts.shape
        cache_len = s + max_new

        t0 = time.time()
        with self.mesh:
            logits, caches, hidden = jax.jit(
                lambda p, batch: M.prefill(p, self.cfg, batch, cache_len=cache_len)
            )(self.params, {"tokens": jnp.asarray(prompts, jnp.int32)})
        jax.block_until_ready(logits)
        self.stats["prefill_s"] += time.time() - t0

        key = jax.random.PRNGKey(sc.seed)
        out_tokens, out_hidden = [], []
        tok = self._pick(logits, hidden, key, 0)
        out_tokens.append(tok)
        t1 = time.time()
        for i in range(max_new - 1):
            pos = jnp.int32(s + i)
            fn = self._decode_fn(caches, tok, pos)
            with self.mesh:
                logits, caches, hidden = fn(self.params, caches, tok, pos)
            key, sub = jax.random.split(key)
            tok = self._pick(logits, hidden, sub, i + 1)
            out_tokens.append(tok)
            out_hidden.append(hidden)
        jax.block_until_ready(tok)
        self.stats["decode_s"] += time.time() - t1
        self.stats["tokens"] += b * max_new
        toks = jnp.stack(out_tokens, axis=1)
        return np.asarray(toks), out_hidden

    def datastore_queue(self) -> DynamicBatcher:
        """The engine's dynamic-batching queue over the kNN-LM datastore,
        opened on first use.  Its searcher owns the datastore's slack state
        across batches; `drain_datastore` republishes the grown snapshot."""
        if self.datastore is None or self.sc.knn is None:
            raise ValueError("datastore_queue needs a kNN-LM datastore")
        if self._ds_queue is None:
            searcher = api.ActiveSearcher.from_index(
                self.datastore, self.sc.knn.grid, plan=self.sc.knn.plan
            )
            self._ds_queue = DynamicBatcher(searcher, k=self.sc.knn.k)
        return self._ds_queue

    def queue_datastore_pairs(self, hiddens, tokens) -> int:
        """Queue ONLINE datastore growth from this engine's own decode
        stream: `hiddens` is the per-step hidden list from `generate`,
        `tokens` the (B, new) emitted tokens.  Pairs (h_t -> token_{t+1})
        enter the insert backlog (applied between search batches — see
        DynamicBatcher); returns the number of pairs queued."""
        if not hiddens:
            return 0
        keys = jnp.concatenate(
            [h.astype(jnp.float32) for h in hiddens], axis=0
        )  # (B*(new-1), d)
        vals = jnp.asarray(tokens[:, 1:], jnp.int32).T.reshape(-1)
        self.datastore_queue().offer_insert(keys, labels=vals)
        return int(keys.shape[0])

    def drain_datastore(self) -> int:
        """Apply the queued inserts (core/mutable.py deltas — no rebuild,
        no PCA re-fit) and publish the grown datastore so the next
        `generate` call searches it.  Returns the rows applied."""
        if self._ds_queue is None:
            return 0
        before = self._ds_queue.stats["inserts_applied"]
        self._ds_queue.drain()
        self.datastore = self._ds_queue.searcher.index
        return self._ds_queue.stats["inserts_applied"] - before

    def extend_datastore(self, hiddens, tokens) -> int:
        """Synchronous grow: queue the decode stream's pairs and drain at
        once.  Returns the number of pairs added."""
        if self.datastore is None or self.sc.knn is None:
            raise ValueError("extend_datastore needs a kNN-LM datastore")
        added = self.queue_datastore_pairs(hiddens, tokens)
        self.drain_datastore()
        return added

    def _pick(self, lm_logits, hidden, key, step):
        if self.datastore is not None and self.sc.knn is not None:
            logp = knn_lm.knn_lm_logits(
                self.datastore, self.sc.knn, hidden.astype(jnp.float32), lm_logits
            )
        else:
            logp = jax.nn.log_softmax(lm_logits, axis=-1)
        if self.sc.greedy:
            return jnp.argmax(logp, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logp / self.sc.temperature, axis=-1
        ).astype(jnp.int32)


def build_datastore_from_model(cfg, params, corpus: np.ndarray, knn_cfg) -> GridIndex:
    """Harvest (hidden_t -> token_{t+1}) pairs from a prefill pass over
    `corpus` (B, S) and build the active-search datastore."""
    @jax.jit
    def hiddens(batch):
        x = M.embed_inputs(params, cfg, batch)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)

        def body(x, block_slice):
            for p in range(cfg.block_period):
                x, _ = M._apply_layer_train(block_slice[p], cfg, p, x, positions)
            return x, None

        if cfg.policy.scan_layers and cfg.n_repeat > 1:
            x, _ = jax.lax.scan(body, x, params["blocks"])
        else:
            for r in range(cfg.n_repeat):
                blk = [jax.tree.map(lambda a: a[r], params["blocks"][p])
                       for p in range(cfg.block_period)]
                x, _ = body(x, blk)
        import repro.models.layers as L
        return L.rms_norm(x, params["final_norm"], cfg.norm_eps)

    h = hiddens({"tokens": jnp.asarray(corpus, jnp.int32)})      # (B, S, d)
    keys = np.asarray(h[:, :-1, :], np.float32).reshape(-1, h.shape[-1])
    vals = corpus[:, 1:].reshape(-1).astype(np.int32)
    return knn_lm.build_datastore(jnp.asarray(keys), jnp.asarray(vals), knn_cfg)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES, default="internlm2-1.8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--knn", action="store_true", help="enable the kNN-LM head")
    ap.add_argument("--datastore-size", type=int, default=8192)
    ap.add_argument(
        "--knn-backend", default="jnp",
        help="registered active-search backend for the datastore "
             "(repro.api.registered_backends(); 'pallas' = batched kernels, "
             "Mosaic-compiled on a TPU, interpreted on the CPU backend)",
    )
    ap.add_argument(
        "--knn-chunk", type=int, default=None,
        help="stream datastore searches through fixed-size query chunks "
             "(bounds kernel VMEM at serve scale; results are identical)",
    )
    ap.add_argument(
        "--knn-online", action="store_true",
        help="grow the kNN-LM datastore DURING serving: after each batch, "
             "delta-insert the decoded (hidden, next-token) pairs "
             "(core/mutable.py) so later batches retrieve from them — no "
             "rebuild between batches",
    )
    args = ap.parse_args()
    if args.knn_online and not args.knn:
        raise SystemExit("--knn-online requires --knn")
    if args.knn:
        # fail on a bad backend name NOW, not after model init + datastore
        # build; count-only backends can't serve searches either
        try:
            impl = api.get_backend(args.knn_backend)
        except ValueError as e:
            raise SystemExit(f"--knn-backend: {e}") from None
        if impl.search is None or impl.requires_mesh:
            # mesh-requiring backends (sharded) implement search() but only
            # on a build_sharded handle; the datastore handle here is
            # from_index-built, so it would fail after model init
            searchable = [n for n in api.registered_backends()
                          if api.get_backend(n).search is not None
                          and not api.get_backend(n).requires_mesh]
            raise SystemExit(
                f"--knn-backend {args.knn_backend!r} cannot serve datastore "
                f"searches; pick one of {searchable}"
            )
        if args.knn_online and not impl.supports_mutation:
            # capability-driven, not name-matched: online growth needs a
            # backend that can serve the refreshed post-insert snapshot
            mutable = [n for n in api.registered_backends()
                       if api.get_backend(n).supports_mutation
                       and not api.get_backend(n).requires_mesh]
            raise SystemExit(
                f"--knn-online: backend {args.knn_backend!r} does not "
                f"support mutation (BackendImpl.supports_mutation); pick "
                f"one of {mutable}"
            )

    enable_compile_cache()
    cfg = get_smoke(args.arch)
    mesh = make_host_mesh(1, 1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    # ONE ExecutionPlan carries every execution knob from the CLI down
    # through KNNLMConfig -> ActiveSearcher; no per-signature re-plumbing
    plan = api.ExecutionPlan(backend=args.knn_backend, chunk_size=args.knn_chunk)
    knn_cfg = knn_lm.KNNLMConfig(plan=plan) if args.knn else None
    datastore = None
    if args.knn:
        corpus = rng.integers(
            0, cfg.vocab_size, size=(args.datastore_size // 64, 65), dtype=np.int32
        )
        datastore = build_datastore_from_model(cfg, params, corpus, knn_cfg)
        print(f"[serve] datastore: {datastore.n_points} keys "
              f"(search backend: {args.knn_backend})")

    engine = Engine(cfg, params, mesh, ServeConfig(knn=knn_cfg), datastore)
    prompts = rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len),
                           dtype=np.int32)
    toks, hiddens = engine.generate(prompts, args.max_new)
    if args.knn_online:
        added = engine.queue_datastore_pairs(hiddens, toks)
        q = engine.datastore_queue()
        print(f"[serve] insert backlog: {q.stats['insert_backlog']} rows "
              f"(peak {q.stats['insert_backlog_peak']})")
        engine.drain_datastore()
        print(f"[serve] datastore grew online: +{added} pairs -> "
              f"{engine.datastore.n_points} keys (no rebuild)")
        prompts2 = rng.integers(
            0, cfg.vocab_size, size=(args.batch, args.prompt_len), dtype=np.int32
        )
        toks, _ = engine.generate(prompts2, args.max_new)
    s = engine.stats
    print(f"[serve] generated {toks.shape} tokens")
    print(
        f"[serve] prefill {s['prefill_s']*1e3:.1f} ms, "
        f"decode {s['decode_s']*1e3:.1f} ms "
        f"({s['tokens']/max(s['decode_s'],1e-9):.1f} tok/s)"
    )


if __name__ == "__main__":
    main()
