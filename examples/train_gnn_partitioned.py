"""End-to-end driver: Distributed-NE-partitioned distributed GNN training.

Spawns 8 host devices, partitions a synthetic graph with the SPMD
Distributed NE, builds the vertex-cut engine, and trains a GIN node
classifier for a few hundred steps with checkpointing — the full pipeline
a real deployment runs (partition → place → train → checkpoint).

  PYTHONPATH=src python examples/train_gnn_partitioned.py
"""
import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np      # noqa: E402
import jax              # noqa: E402

from repro.core import NEConfig, evaluate, partition  # noqa: E402
from repro.apps.engine import build_sharded_graph  # noqa: E402
from repro.graphs.generators import barabasi_albert  # noqa: E402
from repro.launch import gnn_engine as ge  # noqa: E402
from repro.models.gnn import gin  # noqa: E402
from repro.train import optimizer as opt  # noqa: E402
from repro.train.trainer import TrainLoopConfig, run_training  # noqa: E402


def main(steps: int = 300):
    d = len(jax.devices())
    print(f"devices: {d}")
    g = barabasi_albert(2_000, 4, seed=0)
    e = np.asarray(g.edges)
    n = g.num_vertices

    # 1. partition with Distributed NE (single-controller here; the SPMD
    #    variant is exercised in tests/benchmarks)
    res = partition(g, NEConfig(num_partitions=d, seed=0))
    st = evaluate(e, res.edge_part, n, d)
    print(f"partitioned: RF={st.replication_factor:.3f} "
          f"EB={st.edge_balance:.3f}")

    # 2. build the vertex-cut engine + synthetic features/labels
    rng = np.random.default_rng(0)
    feat_dim, n_classes = 16, 4
    w_true = rng.normal(size=(feat_dim, n_classes))
    feats = rng.normal(size=(n, feat_dim)).astype(np.float32)
    labels = (feats @ w_true).argmax(1).astype(np.int32)
    sg = build_sharded_graph(e, res.edge_part, n, d)
    cfg = gin.GINConfig(n_layers=3, d_hidden=32, d_feat=feat_dim,
                        n_classes=n_classes)
    caps = ge.caps_from_sharded_graph(sg, feat_dim, n_classes)
    arrays = ge.engine_arrays(sg, feats, labels, np.ones(n, bool), None)
    arrays.pop("positions", None)

    from repro.dist import compat

    mesh = compat.make_mesh((d,), ("data",))
    loss_fn = ge.make_engine_loss("gin", cfg, caps, mesh, ("data",),
                                  has_positions=False)

    ocfg = opt.OptConfig(lr=3e-3, weight_decay=0.0, warmup_steps=20,
                         total_steps=steps)

    @jax.jit
    def step_fn(params, state, _):
        loss, grads = jax.value_and_grad(loss_fn)(params, arrays)
        params, state, stats = opt.update(grads, state, params, ocfg)
        return params, state, loss, stats["grad_norm"]

    params = gin.init_params(jax.random.PRNGKey(0), cfg)
    state = opt.init(params, ocfg)

    def batches():
        while True:
            yield 0

    tcfg = TrainLoopConfig(total_steps=steps, ckpt_every=100,
                           ckpt_dir="/tmp/repro_gnn_ckpt", log_every=50)
    params, state, hist = run_training(step_fn, params, state, batches(),
                                       tcfg)
    print(f"\nfinal loss: {hist[-1]['loss']:.4f} "
          f"(start {hist[0]['loss']:.4f}) — "
          f"{'LEARNED' if hist[-1]['loss'] < 0.5 * hist[0]['loss'] else 'check config'}")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
