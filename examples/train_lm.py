"""End-to-end LM training driver: a reduced smollm on synthetic data.

Runs a few hundred AdamW steps with the fault-tolerant trainer (checkpoint
+ resume), demonstrating the full train path that the dry-run lowers at
production scale.  ~2M params so a CPU finishes in minutes.

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm.transformer import LMConfig, init_params, loss_fn
from repro.train import optimizer as opt
from repro.train.trainer import TrainLoopConfig, run_training


def synthetic_batches(vocab: int, batch: int, seq: int, seed: int = 0):
    """Markov-ish synthetic stream — learnable structure, not noise."""
    rng = np.random.default_rng(seed)
    trans = rng.integers(0, vocab, size=(vocab, 4))
    while True:
        toks = np.zeros((batch, seq + 1), np.int32)
        toks[:, 0] = rng.integers(0, vocab, batch)
        for t in range(seq):
            choice = rng.integers(0, 4, batch)
            toks[:, t + 1] = trans[toks[:, t], choice]
        yield jnp.asarray(toks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    args = ap.parse_args()

    cfg = LMConfig(name="smollm-tiny", n_layers=4, d_model=128, n_heads=4,
                   n_kv_heads=2, d_ff=384, vocab=512, head_dim=32,
                   dtype=jnp.float32, remat="none")
    params = init_params(jax.random.PRNGKey(0), cfg)
    from repro.models.common import count_params
    print(f"params: {count_params(params):,}")

    ocfg = opt.OptConfig(lr=1e-3, warmup_steps=30, total_steps=args.steps)
    state = opt.init(params, ocfg)

    @jax.jit
    def step_fn(params, state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
        params, state, stats = opt.update(grads, state, params, ocfg)
        return params, state, loss, stats["grad_norm"]

    tcfg = TrainLoopConfig(total_steps=args.steps, ckpt_every=100,
                           ckpt_dir="/tmp/repro_lm_ckpt", log_every=25)
    params, state, hist = run_training(
        step_fn, params, state, synthetic_batches(cfg.vocab, 8, 64), tcfg)
    print(f"\nloss {hist[0]['loss']:.3f} → {hist[-1]['loss']:.3f} "
          f"(uniform = {np.log(cfg.vocab):.3f})")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
