"""End-to-end checkpointable partitioning: spill → partition → kill →
resume → durable artifact → GAS engine, never re-partitioning.

The operational loop a long multi-host run lives by: the graph is
generated straight to the out-of-core store, ingested by host block
ranges, partitioned round by round with a crash-safe snapshot after every
few rounds, "killed" mid-run, resumed bit-identically from the latest
snapshot, and the finished assignment is persisted as a partition
artifact that the GAS engine loads directly.

  PYTHONPATH=src python examples/partition_checkpointed.py
"""
import os
import tempfile

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np      # noqa: E402

import repro.io as rio  # noqa: E402
from repro.apps.algorithms import pagerank  # noqa: E402
from repro.core import NEConfig, evaluate  # noqa: E402
from repro.runtime import (PartitionDriver, host_block_ranges,  # noqa: E402
                           load_artifact)


def main(scale: int = 12, snapshot_every: int = 4):
    cfg = NEConfig(num_partitions=8, seed=0, k_sel=128, edge_chunk=1 << 14)
    with tempfile.TemporaryDirectory() as td:
        # 1. generate straight to the store (never the full list in RAM)
        ef = rio.spill_canonical_rmat(os.path.join(td, "graph"), scale, 8,
                                      seed=3, chunk_size=1 << 12)
        print(f"store: {ef.num_edges} edges, {ef.num_blocks} blocks, "
              f"host ranges (4 hosts): {host_block_ranges(ef, 4)}")

        # 2. partition with snapshots every few rounds
        snap = os.path.join(td, "snapshots")
        drv = PartitionDriver(ef, cfg, snapshot_dir=snap,
                              snapshot_every=snapshot_every)
        while not drv.done and drv.rounds < 6:   # ... then the job dies
            drv.step()
        if not drv.snapshot.rounds():            # converged before interval
            drv.save_snapshot()
        print(f"killed at round {drv.rounds} "
              f"(latest snapshot: round {drv.snapshot.rounds()[-1]})")

        # 3. resume from the latest snapshot — bit-identical continuation
        drv2 = PartitionDriver.resume(ef, cfg, snap,
                                      snapshot_every=snapshot_every)
        print(f"resumed at round {drv2.rounds}")
        res = drv2.run()
        st = evaluate(drv2._edges, res.edge_part, drv2.n,
                      cfg.num_partitions)
        print(f"done: rounds={res.rounds} RF={st.replication_factor:.3f} "
              f"EB={st.edge_balance:.3f}")

        # 4. persist the durable artifact, reload, run PageRank on it
        art_dir = os.path.join(td, "artifact")
        drv2.save_artifact(art_dir)
        loaded = load_artifact(art_dir)
        sg = loaded.sharded_graph()
        pr = pagerank(sg, iters=20)
        print(f"artifact: RF={loaded.replication_factor:.3f}, "
              f"pagerank top vertex = {int(np.argmax(pr))} "
              f"(no re-partitioning)")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
