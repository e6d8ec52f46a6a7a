"""Quickstart: partition a graph with Distributed NE and inspect quality.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import NEConfig, evaluate, partition, theorem1_upper_bound
from repro.core.baselines import dbh, grid_2d, hdrf, random_1d
from repro.core.metrics import comm_volume_model
from repro.graphs.rmat import rmat


def main():
    print("Generating an RMAT graph (Graph500 params, scale 14, EF 16)…")
    g = rmat(14, 16, seed=1)
    e = np.asarray(g.edges)
    p = 32
    print(f"|V|={g.num_vertices:,}  |E|={g.num_edges:,}  |P|={p}")

    cfg = NEConfig(num_partitions=p, alpha=1.1, lam=0.1, seed=0)
    res = partition(g, cfg)
    st = evaluate(e, res.edge_part, g.num_vertices, p)
    ub = theorem1_upper_bound(g.num_vertices, g.num_edges, p)
    print(f"\nDistributed NE:  RF={st.replication_factor:.3f}  "
          f"EB={st.edge_balance:.3f}  rounds={res.rounds}")
    print(f"Theorem 1 upper bound: {ub:.2f}  (RF ≤ UB: "
          f"{st.replication_factor <= ub})")

    print("\nBaselines:")
    for name, fn in (("random", random_1d), ("grid", grid_2d),
                     ("dbh", dbh), ("hdrf", hdrf)):
        rf = evaluate(e, fn(g, p), g.num_vertices, p).replication_factor
        print(f"  {name:9s} RF={rf:.3f}")

    mb = comm_volume_model(st, g.num_vertices, feat_dim=128) / 1e6
    print(f"\nVertex-cut engine traffic per GNN layer at F=128: {mb:.1f} MB"
          f"  (∝ RF — this is why partition quality matters at scale)")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
