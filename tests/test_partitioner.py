"""Unit + property tests for the Distributed NE core (paper §3–§6)."""
import hashlib

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip, don't die at collection
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import NEConfig, evaluate, from_edges, partition, \
    theorem1_upper_bound
from repro.core.baselines import dbh, grid_2d, hdrf, oblivious, random_1d
from repro.core.hybrid import HybridConfig, partition_hybrid
from repro.core.metrics import vertex_replicas
from repro.core.sequential_ne import sequential_ne
from repro.graphs.rmat import rmat
from repro.graphs.generators import erdos_renyi, grid2d, ring_plus_complete
from repro.runtime import PartitionDriver


@pytest.fixture(scope="module")
def small_rmat():
    return rmat(10, 8, seed=3)  # 1024 vertices, ~5.5k edges


@pytest.fixture(scope="module")
def small_result(small_rmat):
    return partition(small_rmat, NEConfig(num_partitions=8, seed=0))


def _check_invariants(g, res, cfg):
    e = np.asarray(g.edges)
    n, m, p = g.num_vertices, g.num_edges, cfg.num_partitions
    # every edge assigned to exactly one partition
    assert res.edge_part.shape == (m,)
    assert (res.edge_part >= 0).all() and (res.edge_part < p).all()
    # replica sets match an independent recomputation from the assignment
    vr = vertex_replicas(e, res.edge_part, n, p)
    np.testing.assert_array_equal(res.vparts.sum(axis=0), vr)
    # edge counts consistent
    np.testing.assert_array_equal(
        res.edges_per_part, np.bincount(res.edge_part, minlength=p))
    st_ = evaluate(e, res.edge_part, n, p)
    # Theorem 1: RF ≤ (|E|+|V|+|P|)/|V|
    assert st_.replication_factor <= theorem1_upper_bound(n, m, p) + 1e-9
    # α-balance with the paper's one-batch overshoot slack
    limit = cfg.alpha * m / p
    max_deg = int(np.asarray(g.degree).max())
    assert st_.max_part_edges <= limit + max_deg + 1


def test_invariants_rmat(small_rmat, small_result):
    _check_invariants(small_rmat, small_result,
                      NEConfig(num_partitions=8, seed=0))


def test_quality_beats_hashing(small_rmat, small_result):
    g = small_rmat
    e = np.asarray(g.edges)
    rf_ne = evaluate(e, small_result.edge_part, g.num_vertices, 8)\
        .replication_factor
    for fn in (random_1d, grid_2d, dbh):
        rf_b = evaluate(e, fn(g, 8), g.num_vertices, 8).replication_factor
        assert rf_ne < rf_b, f"NE {rf_ne} not better than {fn.__name__} {rf_b}"


def test_multi_expansion_tradeoff(small_rmat):
    """Fig. 6: λ=1.0 → far fewer rounds, RF no better than λ=0.1."""
    g = small_rmat
    r_small = partition(g, NEConfig(num_partitions=8, lam=0.1, seed=0))
    r_big = partition(g, NEConfig(num_partitions=8, lam=1.0, seed=0))
    assert r_big.rounds < r_small.rounds
    e = np.asarray(g.edges)
    rf_small = evaluate(e, r_small.edge_part, g.num_vertices, 8)\
        .replication_factor
    rf_big = evaluate(e, r_big.edge_part, g.num_vertices, 8)\
        .replication_factor
    assert rf_small <= rf_big + 0.05


def test_determinism(small_rmat):
    g = small_rmat
    a = partition(g, NEConfig(num_partitions=4, seed=7))
    b = partition(g, NEConfig(num_partitions=4, seed=7))
    np.testing.assert_array_equal(a.edge_part, b.edge_part)


def test_two_hop_ablation(small_rmat):
    """Condition (5) free edges must not hurt quality."""
    g = small_rmat
    e = np.asarray(g.edges)
    with_ = partition(g, NEConfig(num_partitions=8, seed=1, two_hop=True))
    without = partition(g, NEConfig(num_partitions=8, seed=1, two_hop=False))
    rf_w = evaluate(e, with_.edge_part, g.num_vertices, 8).replication_factor
    rf_wo = evaluate(e, without.edge_part, g.num_vertices, 8)\
        .replication_factor
    assert rf_w <= rf_wo + 0.05


def test_theorem2_tightness():
    """Ring+complete construction: RF ≤ UB always; UB is attainable-shaped."""
    g, p = ring_plus_complete(6)
    res = partition(g, NEConfig(num_partitions=p, alpha=1.01, seed=0))
    e = np.asarray(g.edges)
    stt = evaluate(e, res.edge_part, g.num_vertices, p)
    ub = theorem1_upper_bound(g.num_vertices, g.num_edges, p)
    assert stt.replication_factor <= ub + 1e-9


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(12, 60),
    avg=st.floats(1.5, 6.0),
    p=st.sampled_from([2, 3, 4, 8]),
    lam=st.sampled_from([0.1, 0.5, 1.0]),
    seed=st.integers(0, 5),
)
def test_property_invariants(n, avg, p, lam, seed):
    g = erdos_renyi(n, avg, seed=seed)
    if g.num_edges < p:
        return
    cfg = NEConfig(num_partitions=p, lam=lam, seed=seed, k_sel=8,
                   sel_chunk=2, edge_chunk=64)
    res = partition(g, cfg)
    _check_invariants(g, res, cfg)


@pytest.mark.parametrize("fn", [random_1d, grid_2d, dbh, hdrf, oblivious])
def test_baselines_assign_all(small_rmat, fn):
    ep = fn(small_rmat, 8)
    assert ep.shape == (small_rmat.num_edges,)
    assert (ep >= 0).all() and (ep < 8).all()


def test_grid_bound_property(small_rmat):
    """2D hash: a vertex's edges touch ≤ 2√P−1 partitions."""
    g = small_rmat
    p = 16
    ep = grid_2d(g, p)
    e = np.asarray(g.edges)
    for v in np.asarray(g.degree).argsort()[-5:]:
        mask = (e[:, 0] == v) | (e[:, 1] == v)
        assert len(np.unique(ep[mask])) <= 2 * int(np.sqrt(p)) - 1


def test_seed_stability(small_rmat):
    """Paper §7.2: across 5 random seeds the RF relative std err < 5%."""
    g = small_rmat
    e = np.asarray(g.edges)
    rfs = []
    for seed in range(5):
        res = partition(g, NEConfig(num_partitions=8, seed=seed))
        rfs.append(evaluate(e, res.edge_part, g.num_vertices, 8)
                   .replication_factor)
    rfs = np.asarray(rfs)
    rse = rfs.std(ddof=1) / np.sqrt(5) / rfs.mean()
    assert rse < 0.05, (rfs, rse)


def test_sequential_ne_oracle(small_rmat):
    g = small_rmat
    e = np.asarray(g.edges)
    ep = sequential_ne(e, g.num_vertices, 8, seed=0)
    assert (ep >= 0).all()
    rf_seq = evaluate(e, ep, g.num_vertices, 8).replication_factor
    rf_rand = evaluate(e, random_1d(g, 8), g.num_vertices, 8)\
        .replication_factor
    assert rf_seq < rf_rand


# Golden edge assignments: sha256 (first 16 hex digits) of the int32
# ``edge_part``, with ``rounds`` and ``leftover``.  Recorded before the
# one-device run and the hybrid's expansion moved onto the SPMD round, so
# they pin that move as behaviour-preserving.
_GOLDEN_GRAPHS = {
    "rmat10": lambda: rmat(10, 8, seed=3),
    "rmat12": lambda: rmat(12, 16, seed=1),
    "er2000": lambda: erdos_renyi(2000, 8.0, seed=2),
    "grid40": lambda: grid2d(40, 40),
}
_GOLDEN = [
    ("partition", "rmat10", dict(num_partitions=8, seed=0),
     "c33d64818fbf052c", 39, 0),
    ("partition", "rmat12", dict(num_partitions=16, seed=5),
     "4035b9bd21e1effa", 47, 0),
    ("partition", "er2000", dict(num_partitions=8, seed=0, two_hop=False),
     "67a001ae707a25b7", 53, 0),
    ("partition", "grid40", dict(num_partitions=8, seed=1, max_rounds=5),
     "f5d6fe5c2e03a489", 5, 2987),
    ("partition", "rmat12", dict(num_partitions=8, seed=1, edge_chunk=1024),
     "0fa9d44745379625", 54, 0),
    ("hybrid", "rmat12", dict(num_partitions=8, budget_frac=0.5, seed=0),
     "2e3c2e7e4800b96a", 50, 0),
    ("hybrid", "er2000", dict(num_partitions=8, budget_frac=0.3, seed=4),
     "e6981ec71d8a0fbb", 36, 0),
    ("hybrid", "rmat10", dict(num_partitions=16, budget_frac=0.7, seed=1,
                              two_hop=False),
     "e1f7deb3631a333c", 30, 0),
]


@pytest.mark.parametrize("entry,graph,kw,digest,rounds,leftover", _GOLDEN,
                         ids=[f"{e}-{g}-{i}" for i, (e, g, *_) in
                              enumerate(_GOLDEN)])
def test_golden_assignments(entry, graph, kw, digest, rounds, leftover):
    """The public call and the driver give the recorded assignment."""
    g = _GOLDEN_GRAPHS[graph]()
    if entry == "partition":
        cfg = NEConfig(**kw)
        results = (partition(g, cfg),
                   PartitionDriver(g, cfg, num_devices=1).run())
    else:
        cfg = HybridConfig(**kw)
        results = (partition_hybrid(g, cfg),
                   PartitionDriver(g, cfg, mode="hybrid").run())
    for res in results:
        got = hashlib.sha256(np.asarray(res.edge_part, np.int32)
                             .tobytes()).hexdigest()[:16]
        assert (got, res.rounds, res.leftover) == (digest, rounds, leftover)
