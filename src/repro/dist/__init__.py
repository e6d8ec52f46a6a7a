"""Distribution layer: mesh context, sharding rules, SPMD partitioner.

This package is the substrate that turns the single-controller paper
reproduction into the distributed system the paper describes (§4):

  ``compat``          — helpers JAX does not spell directly: the packed
                        OR all-reduce, Auto-axis ``make_mesh``, and the
                        process-level barrier / reductions.
  ``context``         — thread-local (mesh, batch_axes, model_axis)
                        registry used by model code that needs explicit
                        collectives (MoE expert parallelism, row-sharded
                        embedding tables).
  ``sharding``        — logical-axis → ``PartitionSpec`` rule tables
                        (``Rules`` / ``lm_rules``) consumed by the LM
                        transformer and the launch step builders.
  ``partitioner_sm``  — the one NE round: ``partition_spmd`` runs it as
                        a ``shard_map`` program over 2D-hash edge shards
                        with per-round ``SyncVertexAllocations``;
                        ``partition`` is that program on one device.
  ``redistribute``    — all-to-all edge shuffle so partition *p*'s edges
                        land on device *p* (feeds the GAS engine).

See docs/DESIGN-dist.md for the round structure and invariants.
"""
from repro.dist.context import MeshCtx, get_mesh_ctx, mesh_context
from repro.dist.sharding import NO_RULES, Rules, lm_rules

__all__ = ["MeshCtx", "get_mesh_ctx", "mesh_context", "NO_RULES", "Rules",
           "lm_rules"]
