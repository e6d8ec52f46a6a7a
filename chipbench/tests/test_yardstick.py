"""The benchmark's copied yardstick agrees with the program on the CPU:
the Graph500 generator and canonicalization, the 2D hash, rf/eb/vb, and
the reference rounds edge for edge."""
import numpy as np
import pytest

import graphs
import reference


def test_graph500_equals_spill_canonical_rmat(tmp_path):
    from repro.io.spill import spill_canonical_rmat

    for seed in (0, 7, 2**31 + 3):
        with spill_canonical_rmat(tmp_path / str(seed), 10, 16,
                                  seed=seed) as ef:
            want = ef.read_all()
            assert ef.canonical and ef.num_vertices == 1 << 10
        got = graphs.graph500(10, 16, seed)
        np.testing.assert_array_equal(got, want)
        with graphs.write_edgefile(tmp_path / f"{seed}.edges", got,
                                   1 << 10) as ef:
            np.testing.assert_array_equal(ef.read_all(), want)
            assert ef.canonical


def test_grid_device_equals_program_hash():
    from repro.io.csr import grid_assign_host

    edges = graphs.graph500(10, 16, 3)
    for d in (1, 2, 4, 8):
        np.testing.assert_array_equal(graphs.grid_device(edges, d),
                                      grid_assign_host(edges, d))


def test_stats_equal_metrics_evaluate():
    from repro.core.metrics import evaluate

    edges = graphs.graph500(10, 16, 1)
    rng = np.random.default_rng(0)
    for p in (4, 16, 64):
        ep = rng.integers(0, p, edges.shape[0]).astype(np.int32)
        want = evaluate(edges, ep, 1 << 10, p)
        got = reference.stats(edges, ep, 1 << 10, p)
        assert got == {"rf": want.replication_factor,
                       "eb": want.edge_balance, "vb": want.vertex_balance}


NE = dict(num_partitions=16, alpha=1.1, lam=0.1, k_sel=256, max_rounds=4096,
          sel_chunk=8, edge_chunk=1 << 18, two_hop=True, use_pallas=False)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("graph,seed", [("graph500", 0),
                                        ("graph500", 2**31 + 7),
                                        ("grid2d", 5)])
def test_reference_equals_program(tmp_path, graph, seed, devices):
    """The numpy reference reproduces ``PartitionDriver`` round for round:
    assignment, replica map, counts, D_rest and rf, on 1 and 4 devices."""
    from repro.core.partitioner import NEConfig
    from repro.runtime import PartitionDriver

    if graph == "graph500":
        n, edges = 1 << 11, graphs.graph500(11, 16, 0)
    else:       # flat and of high diameter: many rounds, many restarts
        from repro.graphs.generators import grid2d

        n, edges = 24 * 24, np.asarray(grid2d(24, 24).edges)
    ne = dict(NE, seed=seed)
    ef = graphs.write_edgefile(tmp_path / "g.edges", edges, n)
    drv = PartitionDriver(ef, NEConfig(**ne), num_devices=devices)
    ref = reference.Reference(edges, n, ne, devices, mode="spmd")
    st = ref.init()
    for _ in range(3):          # mid-run state after each round
        drv.step()
        ref.step(st)
        np.testing.assert_array_equal(np.asarray(drv.state.degree_rest),
                                      st.degree_rest)
        np.testing.assert_array_equal(np.asarray(drv.state.vparts),
                                      st.vparts)
        assert int(drv.state.remaining) == st.remaining
    res = drv.run()
    while not ref.done(st):
        ref.step(st)
    assert res.rounds == st.rounds and res.leftover == 0
    np.testing.assert_array_equal(res.edge_part, st.edge_part)
    np.testing.assert_array_equal(res.vparts, st.vparts)
    np.testing.assert_array_equal(res.edges_per_part, st.edges_per_part)
    want = reference.stats(edges, st.edge_part, n, 16)
    assert res.stats.replication_factor == want["rf"]
    assert res.stats.edge_balance == want["eb"]
    assert res.stats.vertex_balance == want["vb"]


def test_reference_refuses_other_modes():
    edges = graphs.graph500(8, 16, 0)
    for mode in ("single", "hybrid"):
        with pytest.raises(ValueError, match="spmd"):
            reference.Reference(edges, 1 << 8, dict(NE, seed=0), 1,
                                mode=mode)
