"""Tests for the distribution helpers: 2D-hash edge sharding
(core/graph.py), the leftover cleanup pass, and edge redistribution."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NEConfig, evaluate, theorem1_upper_bound
from repro.core.graph import (exclusive_rank, grid_assign, shard_edges,
                              target_histogram)
from repro.core.partitioner import cleanup_leftovers
from repro.graphs.generators import erdos_renyi
from repro.graphs.rmat import rmat


@pytest.fixture(scope="module")
def graph():
    return rmat(10, 8, seed=3)


# ---------------------------------------------------------------------------
# grid_assign / shard_edges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [1, 2, 4, 6, 8, 12, 16])
def test_grid_assign_in_range(graph, d):
    dev = np.asarray(grid_assign(graph.edges, d))
    assert dev.shape == (graph.num_edges,)
    assert (dev >= 0).all() and (dev < d).all()


def test_grid_assign_deterministic_and_salted(graph):
    a = np.asarray(grid_assign(graph.edges, 8, salt=0))
    b = np.asarray(grid_assign(graph.edges, 8, salt=0))
    c = np.asarray(grid_assign(graph.edges, 8, salt=1))
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()          # a different salt moves some edges


def test_grid_assign_replica_locality(graph):
    """2D hash: a vertex's edges touch at most rows+cols distinct devices —
    the property that makes replica locations computable (paper §4)."""
    d = 16                          # 4×4 grid
    dev = np.asarray(grid_assign(graph.edges, d))
    e = np.asarray(graph.edges)
    deg = np.asarray(graph.degree)
    for v in deg.argsort()[-5:]:
        mask = (e[:, 0] == v) | (e[:, 1] == v)
        assert len(np.unique(dev[mask])) <= 2 * 4 - 1


@pytest.mark.parametrize("d", [1, 3, 8])
def test_shard_edges_roundtrip(graph, d):
    e = np.asarray(graph.edges)
    shards, masks, cap, dev = shard_edges(e, d)
    assert shards.shape == (d, cap, 2)
    assert masks.shape == (d, cap)
    # returned dev matches an independent grid_assign; capacity == max load
    np.testing.assert_array_equal(dev, np.asarray(grid_assign(graph.edges,
                                                              d)))
    counts = np.bincount(dev, minlength=d)
    assert cap == counts.max()
    np.testing.assert_array_equal(masks.sum(axis=1), counts)
    # invalid rows are zeroed
    assert (shards[~masks] == 0).all()
    # every edge appears exactly once across shards, none invented
    def key(x):
        return x[:, 0].astype(np.int64) * graph.num_vertices + x[:, 1]
    got = np.sort(np.concatenate([key(shards[i][masks[i]])
                                  for i in range(d)]))
    np.testing.assert_array_equal(got, np.sort(key(e)))


# ---------------------------------------------------------------------------
# target_histogram / exclusive_rank: the one-hot reductions against numpy
# ---------------------------------------------------------------------------

def _rank_loop(ids, t):
    """Exclusive rank by a Python loop; a negative id reads target 0's."""
    seen = np.zeros(t, np.int64)
    out = np.empty(ids.shape, np.int64)
    for i, c in enumerate(ids):
        out[i] = seen[c] if c >= 0 else seen[0] - 1
        if c >= 0:
            seen[c] += 1
    return out


@pytest.mark.parametrize("length", [1, 300, 1000])
@pytest.mark.parametrize("t", [1, 16, 64, 96])
@pytest.mark.parametrize("kind", ["mixed", "all_negative"])
def test_target_histogram_and_rank_match_numpy(kind, t, length):
    rng = np.random.default_rng(t * 1000 + length)
    if kind == "mixed":   # about a quarter of the items target nothing
        ids = rng.integers(-t // 3 - 1, t, size=length).astype(np.int32)
    else:
        ids = rng.integers(-5, 0, size=length).astype(np.int32)
    hist = np.asarray(target_histogram(jnp.asarray(ids), t))
    assert hist.dtype == np.int32 and hist.shape == (t,)
    np.testing.assert_array_equal(
        hist, np.bincount(ids[ids >= 0], minlength=t))
    np.testing.assert_array_equal(
        np.asarray(exclusive_rank(jnp.asarray(ids), t)), _rank_loop(ids, t))


# ---------------------------------------------------------------------------
# cleanup_leftovers
# ---------------------------------------------------------------------------

def test_cleanup_respects_capacity_when_possible():
    m, p = 40, 4
    limit = 12                      # total capacity 48 > 40: all must fit
    edges = np.stack([np.arange(m), np.arange(m) + 1], axis=1)
    edge_part = np.full(m, -1, np.int32)
    edge_part[:20] = np.arange(20) % p
    counts = np.bincount(edge_part[:20], minlength=p).astype(np.int32)
    counts[0] = 11                  # partition 0 nearly full
    vparts = np.zeros((m + 1, p), bool)
    n_assigned = cleanup_leftovers(edge_part, vparts, counts, edges, p,
                                   limit)
    assert n_assigned == 20
    assert (edge_part >= 0).all()
    assert (counts <= limit).all()  # α-capacity respected — room existed
    # counts stays consistent with the assignment deltas
    np.testing.assert_array_equal(
        counts, np.bincount(edge_part, minlength=p) + [6, 0, 0, 0])


def test_cleanup_overflow_goes_least_loaded():
    m, p = 10, 2
    limit = 3                       # capacity 6 < 10: overflow unavoidable
    edges = np.stack([np.arange(m), np.arange(m) + 1], axis=1)
    edge_part = np.full(m, -1, np.int32)
    counts = np.array([3, 3], np.int32)   # both at capacity already
    vparts = np.zeros((m + 1, p), bool)
    cleanup_leftovers(edge_part, vparts, counts, edges, p, limit)
    assert (edge_part >= 0).all()
    assert abs(int(counts[0]) - int(counts[1])) <= 1  # balanced overflow


def test_cleanup_updates_replica_sets():
    edges = np.array([[0, 1], [2, 3]])
    edge_part = np.array([-1, -1], np.int32)
    counts = np.zeros(2, np.int32)
    vparts = np.zeros((4, 2), bool)
    cleanup_leftovers(edge_part, vparts, counts, edges, 2, limit=10)
    for eid in range(2):
        p = edge_part[eid]
        assert vparts[edges[eid, 0], p] and vparts[edges[eid, 1], p]


# ---------------------------------------------------------------------------
# partition_spmd + redistribute on however many host devices exist
# (the full 8-device run lives in tests/test_spmd.py's subprocess)
# ---------------------------------------------------------------------------

def test_partition_spmd_invariants_host():
    from repro.core.metrics import vertex_replicas
    from repro.dist.partitioner_sm import partition_spmd

    g = erdos_renyi(80, 4.0, seed=1)
    p = 4
    cfg = NEConfig(num_partitions=p, seed=0, k_sel=16, sel_chunk=2,
                   edge_chunk=256)
    res = partition_spmd(g, cfg)
    e = np.asarray(g.edges)
    assert res.edge_part.shape == (g.num_edges,)
    assert (res.edge_part >= 0).all() and (res.edge_part < p).all()
    np.testing.assert_array_equal(
        res.edges_per_part, np.bincount(res.edge_part, minlength=p))
    vr = vertex_replicas(e, res.edge_part, g.num_vertices, p)
    np.testing.assert_array_equal(res.vparts.sum(axis=0), vr)
    stats = evaluate(e, res.edge_part, g.num_vertices, p)
    assert stats.replication_factor <= \
        theorem1_upper_bound(g.num_vertices, g.num_edges, p) + 1e-9


@pytest.mark.parametrize("part_fn", ["partition", "partition_spmd"])
def test_leftover_hatch_via_public_api(part_fn):
    """max_rounds=1 forces the cleanup pass through both partitioners —
    regression for mutating read-only np views of jax outputs."""
    from repro.core import partition
    from repro.dist.partitioner_sm import partition_spmd

    g = erdos_renyi(60, 3.0, seed=2)
    cfg = NEConfig(num_partitions=4, seed=0, max_rounds=1, k_sel=8,
                   sel_chunk=2, edge_chunk=64)
    res = (partition if part_fn == "partition" else partition_spmd)(g, cfg)
    assert res.leftover > 0          # the hatch actually ran
    assert (res.edge_part >= 0).all()
    np.testing.assert_array_equal(
        res.edges_per_part, np.bincount(res.edge_part, minlength=4))


def test_redistribute_numpy_reference():
    from repro.dist.redistribute import redistribute_edges

    rng = np.random.default_rng(0)
    d, c = 4, 7
    shards = rng.integers(0, 50, (d, c, 2)).astype(np.int32)
    masks = rng.random((d, c)) < 0.8
    parts = rng.integers(-1, d, (d, c)).astype(np.int32)  # some invalid
    edges_out, mask_out, dropped = redistribute_edges(shards, masks, parts)
    valid = masks & (parts >= 0) & (parts < d)
    assert dropped == int(masks.sum() - valid.sum())
    # each device receives exactly the rows targeted at it
    for dd in range(d):
        got = edges_out[dd][mask_out[dd]]
        want = np.concatenate([shards[s][valid[s] & (parts[s] == dd)]
                               for s in range(d)])
        np.testing.assert_array_equal(got, want)
