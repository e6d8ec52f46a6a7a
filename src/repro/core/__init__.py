"""Distributed NE — the paper's primary contribution, JAX-native.

Re-exports resolve lazily (PEP 562) so the jax-free submodules —
``epilogue`` (the sharded finalize kernels) and ``metrics`` — stay
importable without jax: the ``bench_memory`` finalize-RSS gate measures
the epilogue in numpy-only child processes.
"""
from __future__ import annotations

import importlib

_EXPORTS = {
    "Graph": "repro.core.graph",
    "as_graph": "repro.core.graph",
    "from_edges": "repro.core.graph",
    "NEConfig": "repro.core.partitioner",
    "PartitionResult": "repro.core.partitioner",
    "HybridConfig": "repro.core.hybrid",
    "degree_threshold": "repro.core.hybrid",
    "hybrid_split": "repro.core.hybrid",
    "partition_hybrid": "repro.core.hybrid",
    "alpha_limit": "repro.core.epilogue",
    "cleanup_leftovers": "repro.core.epilogue",
    "leftover_plan": "repro.core.epilogue",
    "leftover_targets": "repro.core.epilogue",
    "stitch_slices": "repro.core.epilogue",
    "partition": "repro.dist.partitioner_sm",
    "PartitionStats": "repro.core.metrics",
    "evaluate": "repro.core.metrics",
    "stats_from_counts": "repro.core.metrics",
    "theorem1_upper_bound": "repro.core.metrics",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    if name in _EXPORTS:
        value = getattr(importlib.import_module(_EXPORTS[name]), name)
        globals()[name] = value          # cache for subsequent lookups
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
