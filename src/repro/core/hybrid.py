"""HEP-style hybrid edge partitioning (Mayer & Jacobsen 2021, adapted).

The paper's Distributed NE wins on replication factor but pays for it in
memory: the expansion needs the CSR of everything it partitions.  HEP's
observation is that the high-degree tail of a skewed graph is the wrong
place to spend that memory — hub vertices end up replicated almost
everywhere under *any* method, so hashing their edges costs little
quality, while the low-degree body is exactly where neighbor expansion
earns its keep.  ``partition_hybrid`` implements that split under an
explicit memory budget:

1. **threshold** — :func:`degree_threshold` derives the degree cutoff θ
   from the budget τ ∈ (0, 1]: the largest θ such that the adjacency
   slots of all vertices with ``deg ≤ θ`` fit in ``τ · 2M`` slots — the
   NE phase's CSR is the memory the budget bounds.
2. **split** — :func:`hybrid_split` partitions the edge set: an edge is
   *low* iff at least one endpoint has ``deg ≤ θ`` (HEP's rule — the
   edge lives in a low vertex's adjacency list); only hub–hub edges are
   assigned immediately, by the same 2D grid hash as the ``grid_2d``
   baseline (one streamed pass over the store — the full CSR is never
   built).
3. **expansion** — the NE fixed point runs over the low subgraph only,
   through the one NE round (``dist.partitioner_sm._spmd_round``) on a
   one-device mesh, with the round state pre-seeded with the tail
   phase's ``|E_p|`` counts and replica marks: expansion balances around
   the load the hash phase already placed and can grow regions from (and
   two-hop into) the partitions where a vertex's tail edges already
   live.
4. **stitch** — both halves meet in the shared finalize epilogue
   (``core.epilogue.cleanup_leftovers`` water-fills the ``max_rounds``
   leftovers under the *global* α-capacity), so
   :class:`~repro.core.partitioner.PartitionResult`, artifacts and the
   serving layer consume a hybrid run unchanged.

With ``budget_frac=1.0`` the threshold is the maximum degree, the tail
is empty and the run is bit-identical to ``partition`` under the same
seed (asserted by tests/test_hybrid.py) — the hybrid is a strict
generalization, not a fork, of the primary partitioner.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

from repro.core.epilogue import alpha_limit, cleanup_leftovers
from repro.core.graph import Graph, from_edges
from repro.core.metrics import stats_from_counts
from repro.core.partitioner import NEConfig, PartitionResult
from repro.dist.partitioner_sm import (SpmdState, make_edge_mesh,
                                       place_state, run_rounds,
                                       spmd_init_state)
from repro.io.csr import grid_assign_host
from repro.io.edgefile import EdgeFile
from repro.io.stream import degree_indptr, require_canonical
from repro.kernels.ne_round import ops as ne_ops

# the NE hyper-parameters a HybridConfig forwards to the expansion phase
_NE_FIELDS = ("num_partitions", "alpha", "lam", "k_sel", "max_rounds",
              "sel_chunk", "edge_chunk", "two_hop", "seed", "use_pallas")


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Hybrid partitioning hyper-parameters.

    ``budget_frac`` is the memory budget τ: the NE phase may hold at most
    ``τ · 2M`` adjacency slots (τ = 1 degenerates to pure Distributed NE;
    smaller τ hashes a larger tail).  Every other field mirrors
    :class:`~repro.core.partitioner.NEConfig` and is forwarded to the
    expansion phase verbatim, so a hybrid run inherits the NE defaults,
    the fused-kernel switch, and the snapshot-fingerprint stability
    rules unchanged.
    """

    num_partitions: int
    budget_frac: float = 0.5    # τ: NE-phase slot budget as a fraction of 2M
    alpha: float = 1.1
    lam: float = 0.1
    k_sel: int = 256
    max_rounds: int = 4096
    sel_chunk: int = 8
    edge_chunk: int = 1 << 18
    two_hop: bool = True
    seed: int = 0
    grid_salt: int = 0          # tail-hash salt; 0 matches the grid_2d baseline
    use_pallas: bool = None

    def __post_init__(self):
        assert self.num_partitions >= 1
        assert self.alpha > 1.0
        assert 0.0 < self.budget_frac <= 1.0
        if self.use_pallas is None:
            object.__setattr__(self, "use_pallas", ne_ops.env_enabled())

    def ne_config(self) -> NEConfig:
        """The NEConfig of the expansion phase (shared round functions)."""
        return NEConfig(**{f: getattr(self, f) for f in _NE_FIELDS})

    def clamped(self, num_vertices: int) -> "HybridConfig":
        return dataclasses.replace(self, k_sel=min(self.k_sel, num_vertices))


def degree_threshold(degree: np.ndarray, budget_frac: float) -> int:
    """Degree cutoff θ for memory budget τ = ``budget_frac``.

    The largest θ such that ``Σ_{v: deg(v) ≤ θ} deg(v) ≤ τ · Σ_v deg(v)``
    — i.e. the adjacency slots of the θ-low vertex set fit the budget.
    Every low edge is incident to at least one low vertex, so
    ``M_low ≤ τ · 2M`` and the NE phase's CSR holds at most ``2τ`` of
    the full graph's ``2M`` slots.  Floored at 1 so the expansion phase
    always exists; τ = 1 returns the maximum degree (pure NE).
    """
    degree = np.asarray(degree, np.int64)
    total = int(degree.sum())
    if total == 0:
        return 1
    hist = np.bincount(degree)
    slots = np.cumsum(hist * np.arange(hist.size, dtype=np.int64))
    theta = int(np.searchsorted(slots, budget_frac * total, side="right")) - 1
    return max(theta, 1)


class HybridSplit(NamedTuple):
    """Output of :func:`hybrid_split` — everything the expansion phase and
    the stitch need, with no reference back to the source store."""

    low: Graph               # subgraph of low edges (full vertex space)
    low_eids: np.ndarray     # (M_low,) int64 global edge ids of low edges
    edge_part0: np.ndarray   # (M,) int32: tail grid assignments, low = -1
    tail_counts: np.ndarray  # (P,) int64 |E_p| placed by the tail hash
    tail_vparts: np.ndarray  # (N, P) bool replicas created by the tail hash
    threshold: int
    num_vertices: int
    num_edges: int


def _split_arrays(edges: np.ndarray, degree: np.ndarray, n: int,
                  cfg: HybridConfig):
    """Vectorized split of a resident edge array (the in-memory path)."""
    theta = degree_threshold(degree, cfg.budget_frac)
    p = cfg.num_partitions
    lowm = (degree[edges[:, 0]] <= theta) | (degree[edges[:, 1]] <= theta)
    low_eids = np.flatnonzero(lowm).astype(np.int64)
    edge_part0 = np.full(edges.shape[0], -1, np.int32)
    tail_counts = np.zeros(p, np.int64)
    tail_vparts = np.zeros((n, p), bool)
    tail = edges[~lowm]
    if tail.shape[0]:
        part = grid_assign_host(tail, p, salt=cfg.grid_salt)
        edge_part0[~lowm] = part
        tail_counts += np.bincount(part, minlength=p)
        tail_vparts[tail[:, 0], part] = True
        tail_vparts[tail[:, 1], part] = True
    low_edges = np.ascontiguousarray(edges[lowm], dtype=np.int32)
    return (low_edges, low_eids, edge_part0, tail_counts, tail_vparts, theta)


def hybrid_split(source, cfg: HybridConfig) -> HybridSplit:
    """Degree threshold + low/tail split + tail grid assignment.

    ``source`` is a :class:`Graph` or a canonical :class:`EdgeFile`.  The
    store path streams block-by-block — degrees from one index pass
    (``degree_indptr``), the split and the tail hash from a second — so
    the only O(M) allocations are the outputs themselves (the low edge
    list and the (M,) assignment); the full-graph CSR is never built,
    which is where the hybrid's peak-RSS advantage over NE comes from.
    Both paths produce bit-identical splits (asserted by tests).
    """
    p = cfg.num_partitions
    if isinstance(source, Graph):
        edges = np.asarray(source.edges)
        n = source.num_vertices
        degree = np.asarray(source.degree, np.int64)
        (low_edges, low_eids, edge_part0, tail_counts, tail_vparts,
         theta) = _split_arrays(edges, degree, n, cfg)
    elif isinstance(source, EdgeFile):
        require_canonical(source)
        n, m = int(source.num_vertices), int(source.num_edges)
        degree, _ = degree_indptr(source)
        degree = degree.astype(np.int64)
        theta = degree_threshold(degree, cfg.budget_frac)
        edge_part0 = np.full(m, -1, np.int32)
        tail_counts = np.zeros(p, np.int64)
        tail_vparts = np.zeros((n, p), bool)
        low_blocks: list[np.ndarray] = []
        low_eid_blocks: list[np.ndarray] = []
        off = 0
        for blk in source.iter_blocks():
            lowm = ((degree[blk[:, 0]] <= theta)
                    | (degree[blk[:, 1]] <= theta))
            if lowm.any():
                low_blocks.append(
                    np.ascontiguousarray(blk[lowm], dtype=np.int32))
                low_eid_blocks.append(
                    np.flatnonzero(lowm).astype(np.int64) + off)
            tail = blk[~lowm]
            if tail.shape[0]:
                part = grid_assign_host(tail, p, salt=cfg.grid_salt)
                edge_part0[off + np.flatnonzero(~lowm)] = part
                tail_counts += np.bincount(part, minlength=p)
                tail_vparts[tail[:, 0], part] = True
                tail_vparts[tail[:, 1], part] = True
            off += blk.shape[0]
        low_edges = (np.concatenate(low_blocks) if low_blocks
                     else np.zeros((0, 2), np.int32))
        low_eids = (np.concatenate(low_eid_blocks) if low_eid_blocks
                    else np.zeros((0,), np.int64))
    else:
        raise TypeError("hybrid_split takes a Graph or a canonical "
                        f"EdgeFile, got {type(source).__name__}")
    # low edges are a subset of a canonical order, hence still canonical
    low = from_edges(low_edges, num_vertices=n, dedup=False)
    return HybridSplit(low, low_eids, edge_part0, tail_counts, tail_vparts,
                       int(theta), int(n), int(edge_part0.shape[0]))


def low_shard(split: HybridSplit) -> tuple[np.ndarray, np.ndarray]:
    """The low subgraph as one device's edge shard: (1, M_low, 2) edges in
    low-edge order and their (1, M_low) mask — what ``shard_edges`` makes
    of them for one device."""
    edges = np.asarray(split.low.edges, np.int32)
    return edges[None], np.ones((1, edges.shape[0]), bool)


def hybrid_init_state(split: HybridSplit, necfg: NEConfig,
                      mesh) -> SpmdState:
    """NE round state over the low subgraph on a one-device ``mesh``,
    pre-seeded with the tail phase's per-partition edge counts and replica
    marks — expansion balances around (and grows from) what the hash
    already placed.  With an empty tail this is exactly
    ``spmd_init_state`` of :func:`low_shard`."""
    shards, masks = low_shard(split)
    st = spmd_init_state(shards, masks, split.num_vertices, necfg, mesh)
    vparts = split.tail_vparts
    if necfg.use_pallas:    # the round's packed representation
        vparts = ne_ops.pack_bits_np(vparts)
    return place_state(mesh, st._replace(
        vparts=vparts,
        edges_per_part=split.tail_counts.astype(np.int32)))


def hybrid_finalize(state: SpmdState, split: HybridSplit,
                    cfg: HybridConfig) -> PartitionResult:
    """Stitch the two phases through the shared epilogue.

    One device's shard holds the low edges in their order, so its slots
    are the low-edge assignments; they scatter to their global edge ids
    over the tail grid assignments.  The ``max_rounds`` leftovers (always
    low edges — the tail is fully assigned by construction) water-fill
    under the *global* α-capacity via the exact ``cleanup_leftovers``
    every other partitioning path uses.  Counts/replicas already carry
    both phases (the seeded state), so the stats combine is the standard
    one.
    """
    p = cfg.num_partitions
    limit = alpha_limit(cfg.alpha, split.num_edges, p)
    ep_low = np.array(state.edge_part).reshape(-1)
    vparts = np.array(state.vparts)
    if cfg.use_pallas:  # result surface is always (N, P) bool
        vparts = ne_ops.unpack_bits_np(vparts, p)
    counts = np.array(state.edges_per_part)
    leftover = cleanup_leftovers(ep_low, vparts, counts,
                                 np.asarray(split.low.edges), p, limit)
    edge_part = split.edge_part0.copy()
    edge_part[split.low_eids] = ep_low
    stats = stats_from_counts(vparts.sum(axis=0), counts,
                              split.num_vertices)
    return PartitionResult(edge_part, vparts, counts, int(state.rounds),
                           leftover, stats)


def partition_hybrid(source, cfg: HybridConfig) -> PartitionResult:
    """Run hybrid partitioning end to end.

    ``source`` is a Graph or a canonical EdgeFile (the store path splits
    and hashes the tail streamed — the full CSR is never materialized).
    Returns the same :class:`PartitionResult` surface as ``partition``.
    """
    split = hybrid_split(source, cfg)
    cfg = cfg.clamped(split.num_vertices)
    necfg = cfg.ne_config()
    mesh = make_edge_mesh(1)
    state = hybrid_init_state(split, necfg, mesh)
    if split.low.num_edges:
        limit = alpha_limit(cfg.alpha, split.num_edges, cfg.num_partitions)
        state = run_rounds(necfg, limit, split.num_vertices, mesh,
                           *low_shard(split), state)
    return hybrid_finalize(state, split, cfg)


__all__ = ["HybridConfig", "HybridSplit", "degree_threshold",
           "hybrid_finalize", "hybrid_init_state", "hybrid_split",
           "low_shard", "partition_hybrid"]
