"""End-to-end partition serving: spill → partition → artifact → serving
gang → Zipf query storm → QPS / tail latency / fan-out report.

The online half of the pipeline: an RMAT graph is partitioned with NE,
persisted as a durable artifact, and the artifact is brought up as a
two-process serving gang (one server per partition group, replica-map
routing).  A Zipf-skewed client then hammers neighbor queries — the
realistic shape: a few hub vertices absorb most traffic, which is
exactly what the hot-shard LRU exploits — and the script prints
sustained QPS, p50/p99, the cache hit ratio, and the fan-out histogram
whose mean is bounded by the artifact's replication factor (fan-out IS
the replication cost, paid per boundary query).

  PYTHONPATH=src python examples/serve_partition.py
"""
import os
import tempfile

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np      # noqa: E402

import repro.io as rio  # noqa: E402
from repro.core import NEConfig  # noqa: E402
from repro.runtime import PartitionDriver, load_artifact  # noqa: E402
from repro.serve import (GangClient, PartitionService,  # noqa: E402
                         ShardStore, launch_serving_gang)


def main(scale: int = 12, num_partitions: int = 8, num_groups: int = 2,
         n_queries: int = 2000):
    cfg = NEConfig(num_partitions=num_partitions, seed=0, k_sel=128,
                   edge_chunk=1 << 14)
    with tempfile.TemporaryDirectory() as td:
        # 1. generate to the store, partition, persist the artifact
        ef = rio.spill_canonical_rmat(os.path.join(td, "graph"), scale, 8,
                                      seed=3, chunk_size=1 << 12)
        drv = PartitionDriver(ef, cfg)
        drv.run()
        art_dir = os.path.join(td, "artifact")
        drv.save_artifact(art_dir)
        art = load_artifact(art_dir)
        print(f"artifact: {art.num_edges} edges, P={art.num_partitions}, "
              f"RF={art.replication_factor:.3f}, "
              f"boundary={art.boundary_vertices().size} vertices")

        # 2. serve it: one process per partition group
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env = {"PYTHONPATH": src + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        gang = launch_serving_gang(art_dir, num_groups, cache=256,
                                   extra_env=env)
        print(f"gang up: {num_groups} hosts, ports {gang.ports}")

        # 3. Zipf query storm through the replica-map-routed client
        try:
            cli = GangClient(art, gang.ports)
            verts = np.flatnonzero(art.vparts.any(axis=1))
            rng = np.random.default_rng(1)
            ranks = np.minimum(rng.zipf(1.3, size=n_queries) - 1,
                               verts.size - 1)
            import time

            t0 = time.monotonic()
            for v in verts[ranks]:
                cli.neighbors(int(v))
            wall = time.monotonic() - t0
            st = cli.stats()
            print(f"served {st['served']} neighbor queries in {wall:.2f}s "
                  f"→ {st['served'] / wall:.0f} QPS")
            print(f"latency p50={st['p50_ms']:.2f}ms "
                  f"p99={st['p99_ms']:.2f}ms")
            print(f"fan-out histogram {st['fanout_hist']} "
                  f"(mean {st['fanout_mean']:.2f}; per query "
                  f"≤ the vertex's replica count)")
            # per-host serving stats (cache hit ratio from each member)
            for g, hs in enumerate(cli.gang_stats()):
                print(f"  host {g}: served={hs['served']} "
                      f"hit={hs['cache']['hit_ratio']:.3f} "
                      f"partitions={hs['store']['partitions']}")
            # 4. a 2-hop and a PageRank query, routed the same way
            hub = int(verts[ranks[0]])
            print(f"2-hop({hub}) = {cli.k_hop(hub, 2).size} vertices")
            mass = cli.ppr(hub, eps=1e-3)
            top = sorted(mass, key=mass.get, reverse=True)[:3]
            print(f"ppr({hub}) top-3 = {top}")
            gang_nbrs = cli.neighbors(hub)
        finally:
            gang.close()

        # 5. single-process sanity: same artifact, same answers
        svc = PartitionService(ShardStore(art), batch=0)
        got = svc.neighbors(hub)
        print(f"single-process check: neighbors({hub}) = {got.size}, "
              f"gang agrees: {np.array_equal(got, gang_nbrs)}")
        svc.close()


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
