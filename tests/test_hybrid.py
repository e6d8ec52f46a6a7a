"""Hybrid (HEP-style) partitioner tests: the degree split, the τ=1.0
degeneracy to pure NE, Graph↔EdgeFile bit-identity, driver resume, and
the quality sandwich the shoot-out asserts at scale.

HEP's split rule is the min-endpoint one: an edge is *low* iff at least
one endpoint's degree is ≤ θ — only hub–hub edges go to the 2D grid hash,
which is what keeps hybrid RF close to NE's while the NE working set
shrinks to the τ-budgeted low-degree subgraph.
"""
import os

import numpy as np
import pytest

from repro.core import NEConfig, evaluate, partition
from repro.core.baselines import grid_2d
from repro.core.hybrid import (HybridConfig, degree_threshold, hybrid_split,
                               partition_hybrid)
from repro.graphs.rmat import rmat
from repro.io.stream import canonicalize_stream
from repro.runtime import PartitionDriver, SnapshotMismatch

P = 8
CFG = HybridConfig(num_partitions=P, budget_frac=0.25, seed=0)


@pytest.fixture(scope="module")
def g():
    return rmat(11, 8, seed=2)


@pytest.fixture(scope="module")
def ef(g, tmp_path_factory):
    # same edges as the in-memory graph (the spilled-RMAT generator uses a
    # different chunked RNG stream, so build the EdgeFile from g directly)
    td = tmp_path_factory.mktemp("hybrid_ef")
    return canonicalize_stream(np.asarray(g.edges),
                               os.path.join(td, "g.edges"),
                               num_vertices=g.num_vertices, tmpdir=str(td))


# -- degree threshold -------------------------------------------------------

def test_threshold_full_budget_is_dmax(g):
    deg = np.asarray(g.degree)
    assert degree_threshold(deg, 1.0) == int(deg.max())


def test_threshold_monotone_and_floored(g):
    deg = np.asarray(g.degree)
    taus = (1e-9, 0.1, 0.25, 0.5, 1.0)
    ths = [degree_threshold(deg, t) for t in taus]
    assert ths == sorted(ths)
    assert ths[0] >= 1          # floor: never split every vertex out


def test_threshold_budget_bound(g):
    """Σ_{deg≤θ} deg ≤ τ·2M — the slot bound the NE CSR budget rests on."""
    deg = np.asarray(g.degree)
    for tau in (0.1, 0.25, 0.5):
        theta = degree_threshold(deg, tau)
        assert deg[deg <= theta].sum() <= tau * 2 * g.num_edges + 1e-9


# -- the split --------------------------------------------------------------

def test_split_min_endpoint_rule(g):
    split = hybrid_split(g, CFG)
    e = np.asarray(g.edges)
    deg = np.asarray(g.degree)
    low = (deg[e[:, 0]] <= split.threshold) | (deg[e[:, 1]] <= split.threshold)
    np.testing.assert_array_equal(np.flatnonzero(low), split.low_eids)
    # low edges pending (-1); tail already grid-assigned into [0, P)
    assert (split.edge_part0[split.low_eids] == -1).all()
    tail = split.edge_part0[split.edge_part0 >= 0]
    assert tail.size == g.num_edges - split.low_eids.size
    assert (tail < P).all()


def test_split_tail_is_grid_2d(g):
    """The hub–hub tail must be bit-compatible with ``grid_2d`` at the
    same salt — that is what makes the shoot-out's hybrid-vs-grid RF
    comparison an apples-to-apples one."""
    split = hybrid_split(g, CFG)
    ref = grid_2d(g, P, seed=CFG.grid_salt)
    tail = split.edge_part0 >= 0
    np.testing.assert_array_equal(split.edge_part0[tail], ref[tail])


def test_split_counts_and_replicas_consistent(g):
    split = hybrid_split(g, CFG)
    tail = split.edge_part0 >= 0
    np.testing.assert_array_equal(
        split.tail_counts,
        np.bincount(split.edge_part0[tail], minlength=P))
    e = np.asarray(g.edges)[tail]
    expect = np.zeros((g.num_vertices, P), bool)
    expect[e[:, 0], split.edge_part0[tail]] = True
    expect[e[:, 1], split.edge_part0[tail]] = True
    np.testing.assert_array_equal(split.tail_vparts, expect)


def test_split_edgefile_matches_graph(g, ef):
    a, b = hybrid_split(g, CFG), hybrid_split(ef, CFG)
    assert a.threshold == b.threshold
    np.testing.assert_array_equal(a.low_eids, b.low_eids)
    np.testing.assert_array_equal(a.edge_part0, b.edge_part0)
    np.testing.assert_array_equal(np.asarray(a.low.edges),
                                  np.asarray(b.low.edges))


# -- end-to-end quality + degeneracy ---------------------------------------

def test_full_budget_is_pure_ne(g):
    """τ=1.0 ⇒ θ=dmax ⇒ the whole graph is the low subgraph and hybrid
    is bit-identical to ``partition()``."""
    ne = partition(g, NEConfig(num_partitions=P, seed=0))
    hy = partition_hybrid(g, HybridConfig(num_partitions=P,
                                          budget_frac=1.0, seed=0))
    np.testing.assert_array_equal(hy.edge_part, ne.edge_part)
    np.testing.assert_array_equal(hy.vparts, ne.vparts)
    np.testing.assert_array_equal(hy.edges_per_part, ne.edges_per_part)
    assert hy.rounds == ne.rounds and hy.leftover == ne.leftover


def test_rf_between_ne_and_grid(g):
    """The quality sandwich: NE ≤ hybrid ≤ grid on replication factor —
    the same claim the CI shoot-out asserts on the anchor graphs."""
    e = np.asarray(g.edges)

    def rf(ep):
        return evaluate(e, ep, g.num_vertices, P).replication_factor

    rf_ne = rf(partition(g, NEConfig(num_partitions=P, seed=0)).edge_part)
    rf_hy = rf(partition_hybrid(g, CFG).edge_part)
    rf_grid = rf(grid_2d(g, P, seed=CFG.grid_salt))
    assert rf_ne <= rf_hy + 1e-9 and rf_hy <= rf_grid + 1e-9


def test_result_invariants_and_stats(g):
    res = partition_hybrid(g, CFG)
    assert (res.edge_part >= 0).all() and (res.edge_part < P).all()
    np.testing.assert_array_equal(
        res.edges_per_part, np.bincount(res.edge_part, minlength=P))
    st = evaluate(np.asarray(g.edges), res.edge_part, g.num_vertices, P)
    assert res.stats is not None
    assert abs(res.stats.replication_factor - st.replication_factor) < 1e-9
    assert abs(res.stats.edge_balance - st.edge_balance) < 1e-9


def test_edgefile_result_matches_graph(g, ef):
    a, b = partition_hybrid(g, CFG), partition_hybrid(ef, CFG)
    np.testing.assert_array_equal(a.edge_part, b.edge_part)


def test_packed_replica_sets_bit_identical(g):
    """The expansion keeps ``use_pallas``'s packed replica sets, seeded
    from the tail, and the result matches the bool representation's."""
    import dataclasses

    a = partition_hybrid(g, CFG)
    b = partition_hybrid(g, dataclasses.replace(CFG, use_pallas=True))
    np.testing.assert_array_equal(a.edge_part, b.edge_part)
    np.testing.assert_array_equal(a.vparts, b.vparts)
    np.testing.assert_array_equal(a.edges_per_part, b.edges_per_part)
    assert a.rounds == b.rounds


# -- driver: run / kill / resume -------------------------------------------

def test_driver_matches_fire_and_forget(g, tmp_path):
    drv = PartitionDriver(g, CFG, mode="hybrid", snapshot_dir=tmp_path,
                          snapshot_every=1, keep=100_000)
    got = drv.run()
    ref = partition_hybrid(g, CFG)
    np.testing.assert_array_equal(got.edge_part, ref.edge_part)
    assert got.rounds == ref.rounds


def test_resume_bit_identity(g, tmp_path):
    """Kill after round k, resume from the snapshot: bit-identical final
    assignment — the inherited driver contract, now for hybrid mode."""
    full = PartitionDriver(g, CFG, mode="hybrid", snapshot_dir=tmp_path,
                           snapshot_every=1, keep=100_000)
    ref = full.run()
    kill_at = min(3, full.rounds - 1) or 1
    drv = PartitionDriver.resume(g, CFG, tmp_path, round_k=kill_at,
                                 mode="hybrid")
    assert drv.rounds == kill_at
    got = drv.run()
    np.testing.assert_array_equal(got.edge_part, ref.edge_part)
    np.testing.assert_array_equal(got.vparts, ref.vparts)
    assert got.rounds == ref.rounds and got.leftover == ref.leftover


def test_resume_wrong_budget_fails(g, tmp_path):
    PartitionDriver(g, CFG, mode="hybrid", snapshot_dir=tmp_path,
                    snapshot_every=1).run()
    other = HybridConfig(num_partitions=P, budget_frac=0.5, seed=0)
    with pytest.raises(SnapshotMismatch):
        PartitionDriver.resume(g, other, tmp_path, mode="hybrid")


def test_driver_rejects_ne_config_for_hybrid(g):
    with pytest.raises(TypeError):
        PartitionDriver(g, NEConfig(num_partitions=P), mode="hybrid")


def test_artifact_roundtrip(g, tmp_path):
    from repro.runtime import load_artifact

    drv = PartitionDriver(g, CFG, mode="hybrid")
    res = drv.run()
    drv.save_artifact(tmp_path / "art")
    back = load_artifact(tmp_path / "art")
    np.testing.assert_array_equal(back.edge_part, res.edge_part)
    np.testing.assert_array_equal(back.edges, np.asarray(g.edges))
