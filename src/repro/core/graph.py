"""Undirected graph container in CSR form, JAX-native.

The paper stores the input graph 2D-hash edge-partitioned in CSR across
allocation processes (§4 "Data Structure").  We keep the same canonical
representation: an undirected edge list expanded into 2M directed slots,
sorted by source vertex, with an ``edge_id`` column mapping each directed
slot back to its undirected edge.  All partitioner state is keyed either
per-undirected-edge (allocation) or per-vertex (replica sets / D_rest).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.io.compress import PackedCSR
from repro.io.csr import canonicalize_host, csr_from_canonical
from repro.io.edgefile import EdgeFile
from repro.io.stream import graph_from_edgefile

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected graph, CSR over directed slots.

    Attributes:
      edges:    (M, 2) int32 undirected edge endpoints (deduplicated, no loops).
      indptr:   (N+1,) int32 CSR row pointers over the 2M directed slots.
      adj_dst:  (2M,) int32 destination vertex of each directed slot.
      adj_eid:  (2M,) int32 undirected edge id of each directed slot.
      slot_src: (2M,) int32 source vertex of each directed slot (CSR-expanded).
      degree:   (N,) int32 vertex degrees.
    """

    edges: Array
    indptr: Array
    adj_dst: Array
    adj_eid: Array
    slot_src: Array
    degree: Array

    @property
    def num_vertices(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def num_edges(self) -> int:
        return int(self.edges.shape[0])

    @property
    def num_slots(self) -> int:
        return int(self.adj_dst.shape[0])


# host-side canonicalization shared with the streaming store (repro.io):
# one implementation is what keeps stream-built CSRs bit-identical
canonicalize_edges = canonicalize_host


def from_edges(edges: np.ndarray, num_vertices: int | None = None,
               dedup: bool = True) -> Graph:
    """Build a Graph from an undirected edge list (host-side numpy)."""
    if dedup:
        edges, n = canonicalize_edges(edges, num_vertices)
    else:
        edges = np.asarray(edges, dtype=np.int32)
        n = int(num_vertices if num_vertices is not None
                else (edges.max() + 1 if edges.size else 0))
    a = csr_from_canonical(edges, n)
    return Graph(
        edges=jnp.asarray(a.edges),
        indptr=jnp.asarray(a.indptr),
        adj_dst=jnp.asarray(a.adj_dst),
        adj_eid=jnp.asarray(a.adj_eid),
        slot_src=jnp.asarray(a.slot_src),
        degree=jnp.asarray(a.degree),
    )


def as_graph(source, num_vertices: int | None = None) -> Graph:
    """Coerce any graph source to an in-memory :class:`Graph`.

    Accepts a Graph (returned as-is), an edge ndarray, an
    ``repro.io.EdgeFile`` (streamed through the bit-identical out-of-core
    builder) or an ``repro.io.PackedCSR`` (per-shard decompression).  The
    partitioners and the bench harness route their inputs through this.
    """
    if isinstance(source, Graph):
        return source
    if isinstance(source, np.ndarray):
        return from_edges(source, num_vertices)
    if isinstance(source, EdgeFile):
        return graph_from_edgefile(source, num_vertices=num_vertices)
    if isinstance(source, PackedCSR):
        if (num_vertices is not None
                and num_vertices != source.num_vertices):
            raise ValueError(f"num_vertices={num_vertices} conflicts with "
                             f"the packed file's {source.num_vertices}")
        return source.to_graph()
    raise TypeError(f"cannot build a Graph from {type(source).__name__}")


def to_networkx(g: Graph):
    import networkx as nx

    gx = nx.Graph()
    gx.add_nodes_from(range(g.num_vertices))
    gx.add_edges_from(np.asarray(g.edges).tolist())
    return gx


def target_histogram(ids: Array, num_targets: int) -> Array:
    """(T,) int32 count of the items per target id; negative ids count nowhere.

    A dense one-hot compare-and-sum over the items: K·T compare-adds that
    stream through the vector units, where an XLA scatter-add into T
    entries serializes its colliding updates on the TPU (about 9 ns per
    item, whatever T).  Written for the small T of the partition axis.
    Pass ids that are already -1 where an item counts nowhere: a separate
    weight array leaves a K-long temporary behind.
    """
    targets = jnp.arange(num_targets, dtype=ids.dtype)
    return jnp.sum(ids[:, None] == targets[None, :], axis=0, dtype=jnp.int32)


def exclusive_rank(cand: Array, num_targets: int) -> Array:
    """Per-item exclusive rank among earlier items with the same target.

    ``cand``: (K,) int32 target ids, negatives meaning "no target".
    Returns (K,) int32: how many earlier items share item i's target —
    the building block of quota-limited allocation (item i fits iff
    ``rank[i] < quota[cand[i]]``) and of stable send-buffer slotting.
    Value at negative-target items is that of target 0; guard with the
    candidate mask as the callers do.

    The running count is the cumulative sum of the (T, K) one-hot along
    the items, and the lookup selects item i's row and sums along T: K·T
    work and no per-item gather, which suits the small T of the partition
    and device axes.
    """
    targets = jnp.arange(num_targets, dtype=cand.dtype)[:, None]
    running = jnp.cumsum((cand[None, :] == targets).astype(jnp.int32), axis=1)
    own = jnp.maximum(cand, 0)[None, :] == targets
    return jnp.sum(jnp.where(own, running, 0), axis=0) - 1


# ---------------------------------------------------------------------------
# 2D-hash initial distribution (paper §4): edges are uniquely assigned to an
# allocation process from a √D×√D process grid by hashing both endpoints, so
# replica locations of a vertex are *computable* from its id (no metadata).
# ---------------------------------------------------------------------------

def _mix(x: Array) -> Array:
    """Cheap deterministic integer hash (xorshift-multiply, 32-bit)."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash_u32(x: Array, salt: int = 0) -> Array:
    return _mix(x.astype(jnp.uint32) + jnp.uint32(0x9E3779B9) * jnp.uint32(salt))


def grid_assign(edges: Array, num_devices: int, rows: int | None = None,
                salt: int = 0) -> Array:
    """2D-hash (grid) edge→device assignment.  Returns (M,) int32 device ids."""
    r = rows or int(np.floor(np.sqrt(num_devices)))
    while num_devices % r:
        r -= 1
    c = num_devices // r
    hu = hash_u32(edges[:, 0], salt) % jnp.uint32(r)
    hv = hash_u32(edges[:, 1], salt + 1) % jnp.uint32(c)
    return (hu.astype(jnp.int32) * c + hv.astype(jnp.int32))


def shard_edges(edges: np.ndarray, num_devices: int, salt: int = 0,
                ) -> tuple[np.ndarray, np.ndarray, int, np.ndarray]:
    """Host-side 2D-hash distribution into equal-length padded shards.

    Returns (shards, masks, capacity, dev): shards is (D, C, 2) int32 with
    invalid rows = 0, masks is (D, C) bool, and dev is the (M,) int32
    per-edge device assignment (``grid_assign``) so callers can stitch
    shard-order results back to edge order without rehashing.
    """
    dev = np.asarray(grid_assign(jnp.asarray(edges), num_devices, salt=salt))
    counts = np.bincount(dev, minlength=num_devices)
    cap = int(counts.max()) if counts.size else 1
    shards = np.zeros((num_devices, cap, 2), np.int32)
    masks = np.zeros((num_devices, cap), bool)
    for d in range(num_devices):
        rows = edges[dev == d]
        shards[d, : rows.shape[0]] = rows
        masks[d, : rows.shape[0]] = True
    return shards, masks, cap, dev.astype(np.int32)
