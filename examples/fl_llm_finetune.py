"""Beyond-paper example: VAFL federating *language models*.

The FL runtime is model-agnostic (clients are opaque pytrees) — here each
client locally fine-tunes a small transformer LM on its own token stream
(different Markov structures per client = genuinely non-IID corpora), the
server gates uploads with Eq. 1/2 exactly as for the MNIST CNN.  This is
the cross-silo LLM story of DESIGN.md §2 run end-to-end on CPU.

    PYTHONPATH=src python examples/fl_llm_finetune.py [--rounds 6] \
        [--arch minicpm_2b] [--clients 3]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.core import Federation
from repro.core.client import LocalSpec
from repro.core.metrics import ccr
from repro.data.partition import FederatedData
from repro.data.synthetic import token_stream
from repro.models import decoder
from repro.models.registry import get_smoke_config


def make_lm_loss(cfg):
    def loss_fn(params, batch):
        toks = batch["images"]                       # (B, S) int32 tokens
        w = batch.get("weights")
        logits, aux = decoder.forward(cfg, params, toks[:, :-1], remat=False)
        labels = toks[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        nll = jnp.mean(nll, axis=-1)                 # per sequence
        if w is not None:
            loss = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
        else:
            loss = jnp.mean(nll)
        return loss, {}
    return loss_fn


def make_lm_evaluator(cfg, test_tokens):
    xt = jnp.asarray(test_tokens)

    @jax.jit
    def evaluate(params):
        logits, _ = decoder.forward(cfg, params, xt[:, :-1], remat=False)
        pred = jnp.argmax(logits, axis=-1)
        return jnp.mean((pred == xt[:, 1:]).astype(jnp.float32))
    return evaluate


def build_federation(cfg, n_clients, seqs_per_client=48, seq_len=48):
    streams = []
    for c in range(n_clients):
        # one shared corpus structure, disjoint per-silo shards
        toks, _ = token_stream(seqs_per_client, seq_len, cfg.vocab_size,
                               seed=1000 + 17 * c, structure_seed=7)
        streams.append(toks)
    images = np.stack(streams).astype(np.int32)      # (N, M, S)
    N, M, _ = images.shape
    return FederatedData(images=images,
                         labels=np.zeros((N, M), np.int32),
                         mask=np.ones((N, M), np.float32),
                         counts=np.full(N, M, np.int32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm_2b")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=6)
    args = ap.parse_args()
    enable_compile_cache()

    # narrow vocab so the Markov table is learnable within the demo budget
    cfg = get_smoke_config(args.arch).replace(vocab_size=128)
    loss_fn = make_lm_loss(cfg)
    test_toks, _ = token_stream(32, 48, cfg.vocab_size, seed=7,
                                structure_seed=7)
    evaluate = make_lm_evaluator(cfg, test_toks)
    fed = build_federation(cfg, args.clients)

    results = {}
    for alg in ("afl", "vafl"):
        # explicit-fns mode of the Federation facade: any workload whose
        # clients are opaque pytrees plugs in via its own loss/evaluator
        federation = Federation(
            data=fed, algorithm=alg,
            init_params_fn=lambda k: decoder.init_params(cfg, k),
            loss_fn=loss_fn, evaluate_fn=evaluate,
            local=LocalSpec(batch_size=8, local_epochs=1,
                            local_rounds=2, lr=0.5),
            target_acc=0.15)
        print(f"\n=== {alg.upper()} (federated LM fine-tune, "
              f"{args.clients} silos) ===")
        results[alg] = federation.run(rounds=args.rounds, verbose=True)

    afl, vafl = results["afl"], results["vafl"]
    print(f"\nAFL : uploads={afl.comm.model_uploads} "
          f"next-token acc={afl.best_acc:.3f}")
    print(f"VAFL: uploads={vafl.comm.model_uploads} "
          f"next-token acc={vafl.best_acc:.3f} "
          f"CCR={ccr(afl.comm.model_uploads, vafl.comm.model_uploads):.2%}")


if __name__ == "__main__":
    main()
