"""Serving with the paper's technique as a first-class feature: a kNN-LM head
whose datastore is searched with ACTIVE SEARCH at every decode step.

  PYTHONPATH=src python examples/serve_knn_lm.py

Demonstrates the measurable effect of retrieval: after training briefly on a
deterministic bigram corpus, the kNN datastore (memorizing exact continuations)
sharpens next-token predictions on held-out text from the same chain —
held-out NLL improves vs the plain LM head (the margin grows with datastore
coverage and model quality; at this demo scale it is small but consistent).
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.core import knn_lm
from repro.data.pipeline import DataConfig, synth_batch
from repro.launch.mesh import make_host_mesh
from repro.launch.lm_serve import Engine, ServeConfig, build_datastore_from_model
from repro.launch.train import TrainConfig, train_loop
from repro.models import model as M


def nll_of(logp, labels):
    gold = np.take_along_axis(np.asarray(logp), np.asarray(labels)[:, None], 1)
    return float(-gold.mean())


def main():
    cfg = get_smoke("internlm2-1.8b")
    mesh = make_host_mesh(1, 1)

    # 1. train until the LM has learned the chain (hidden states then
    # separate contexts, which is what the datastore keys index)
    print("[example] training 400 steps on the Markov-chain corpus ...")
    out = train_loop(cfg, TrainConfig(steps=400, batch=8, seq=64, log_every=100,
                                      lr=1e-3), mesh)
    params = out["state"]["params"]

    # 2. harvest the datastore from the model's own prefill pass
    dc = DataConfig(global_batch=16, seq_len=64, vocab_size=cfg.vocab_size, seed=7)
    corpus = np.concatenate(
        [synth_batch(dc, s)["tokens"] for s in range(8)], axis=0
    )
    knn_cfg = knn_lm.KNNLMConfig(k=8, lam=0.3)
    store = build_datastore_from_model(cfg, params, corpus, knn_cfg)
    print(f"[example] datastore: {store.n_points} (hidden -> next-token) pairs")

    # 3. held-out evaluation: same chain, unseen step indices
    held = synth_batch(dataclasses_replace_seed(dc, 7), 999)
    tokens = jnp.asarray(held["tokens"][:8])
    logits, _, hidden = M.prefill(params, cfg, {"tokens": tokens[:, :-1]},
                                  cache_len=tokens.shape[1])
    labels = tokens[:, -1]

    lm_logp = jax.nn.log_softmax(logits, axis=-1)
    knn_logp = knn_lm.knn_lm_logits(store, knn_cfg, hidden.astype(jnp.float32),
                                    logits)
    print(f"[example] held-out NLL  plain LM: {nll_of(lm_logp, labels):.4f}")
    print(f"[example] held-out NLL  kNN-LM  : {nll_of(knn_logp, labels):.4f}")

    # 4. batched generation through the serving engine
    engine = Engine(cfg, params, mesh,
                    ServeConfig(knn=knn_cfg, max_new_tokens=16), datastore=store)
    prompts = np.asarray(tokens[:4, :16])
    toks, _ = engine.generate(prompts)
    s = engine.stats
    print(f"[example] generated {toks.shape}; decode "
          f"{s['tokens']/max(s['decode_s'],1e-9):.1f} tok/s")


def dataclasses_replace_seed(dc, seed):
    import dataclasses
    return dataclasses.replace(dc, seed=seed)


if __name__ == "__main__":
    main()
