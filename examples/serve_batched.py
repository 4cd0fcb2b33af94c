"""Batched serving example: decode a batch of requests against three
architecture families (GQA KV cache, MLA compressed cache, RWKV O(1)
state) and print per-family cache footprints — the serving-side story the
decode_32k / long_500k dry-run shapes exercise at production scale.

    PYTHONPATH=src python examples/serve_batched.py [--gen 12]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.common.pytree import tree_bytes
from repro.launch.steps import make_serve_step
from repro.models import decoder
from repro.models.registry import get_smoke_config


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    for arch in ("starcoder2_3b", "minicpm3_4b", "rwkv6_3b"):
        cfg = get_smoke_config(arch)
        params = decoder.init_params(cfg, jax.random.key(0))
        cache_len = 96
        cache = decoder.init_cache(cfg, params, args.batch, cache_len)
        step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
        rng = np.random.RandomState(0)
        prompt = rng.randint(0, cfg.vocab_size, size=(args.batch, 8)).astype(np.int32)
        logits = None
        for t in range(8):
            logits, cache = step(params, cache, jnp.asarray(prompt[:, t:t+1]),
                                 jnp.int32(t))
        toks = []
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        for t in range(8, 8 + args.gen):
            toks.append(np.asarray(tok)[:, 0])
            logits, cache = step(params, cache, tok, jnp.int32(t))
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        kb = tree_bytes(cache) / 1024
        print(f"{arch:16s} cache={kb:9.1f} KiB for {args.batch}x{cache_len} "
              f"slots  first-request tokens: {np.stack(toks,1)[0][:8]}")
    print("\n(full-attention caches grow with context; MLA stores only "
          "kv_lora+rope per token; RWKV/Mamba state is O(1))")


if __name__ == "__main__":
    main()
