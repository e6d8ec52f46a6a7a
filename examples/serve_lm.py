"""Batched LM serving demo: prefill + KV-cache decode loop.

  PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm.transformer import LMConfig, init_params
from repro.models.lm.serve import ServeConfig, serve_batch


def main():
    cfg = LMConfig(name="demo", n_layers=4, d_model=128, n_heads=4,
                   n_kv_heads=2, d_ff=384, vocab=512, head_dim=32,
                   dtype=jnp.float32, remat="none")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(4, 8)).astype(np.int32)
    print("prompts:", prompts.tolist())
    out = serve_batch(params, prompts, cfg,
                      ServeConfig(max_new_tokens=16, cache_len=64,
                                  temperature=0.7))
    print("completions:")
    for row in out:
        print(" ", row.tolist())


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
