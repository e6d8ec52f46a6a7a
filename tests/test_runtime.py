"""repro.runtime tests: round-stepping bit-identity, snapshot/resume,
partition artifacts, multi-host ingestion, sharded checkpoints.

The resume contract under test is the ISSUE's acceptance criterion: a run
killed after round k and resumed from its latest snapshot produces
bit-identical vparts and edge assignments to an uninterrupted run, and the
saved artifact reloads into the GAS path without re-partitioning.
"""
import os

import numpy as np
import pytest

from repro.core import NEConfig, evaluate, partition
from repro.dist.partitioner_sm import partition_spmd
from repro.graphs.rmat import rmat
from repro.io.stream import shard_edges_stream
from repro.runtime import (PartitionDriver, SnapshotMismatch,
                           config_fingerprint, graph_fingerprint,
                           host_block_ranges, ingest_edgefile, load_artifact,
                           save_artifact)
from repro.runtime.snapshot import RunSnapshot, ShardedCheckpointManager

SCALE = 12          # RMAT scale for the resume bit-identity criterion
CFG = NEConfig(num_partitions=8, seed=0, k_sel=64, edge_chunk=1 << 12)


@pytest.fixture(scope="module")
def graph12():
    return rmat(SCALE, 8, seed=3)


@pytest.fixture(scope="module")
def snapped_run(graph12, tmp_path_factory):
    """One uninterrupted driver run with a snapshot after every round."""
    snap_dir = tmp_path_factory.mktemp("runtime") / "snap"
    drv = PartitionDriver(graph12, CFG, snapshot_dir=snap_dir,
                          snapshot_every=1, keep=100_000)
    res = drv.run()
    return drv, res, snap_dir


# ---------------------------------------------------------------------------
# driver == fire-and-forget jits
# ---------------------------------------------------------------------------

def test_driver_bit_identical_to_partition_spmd(graph12, snapped_run):
    """Round stepping reuses the exact traced round function, so the
    state machine is bit-identical to the whole-run while_loop."""
    _, res, _ = snapped_run
    ref = partition_spmd(graph12, CFG)
    np.testing.assert_array_equal(res.edge_part, ref.edge_part)
    np.testing.assert_array_equal(res.vparts, ref.vparts)
    np.testing.assert_array_equal(res.edges_per_part, ref.edges_per_part)
    assert res.rounds == ref.rounds
    assert res.leftover == ref.leftover


# four host devices need a process of their own: the device count is
# fixed before jax starts
_LAYOUT_CHECK = """
import jax
from repro.core import NEConfig
from repro.dist.partitioner_sm import place_state, spmd_round_step
from repro.graphs.rmat import rmat
from repro.runtime import PartitionDriver

assert len(jax.devices()) == 4
cfg = NEConfig(num_partitions=8, seed=0, k_sel=32, edge_chunk=1 << 10)
for d in (1, 4):
    drv = PartitionDriver(rmat(9, 8, seed=1), cfg, num_devices=d)
    placed = [x.sharding for x in place_state(drv.mesh, drv.state)]
    before = spmd_round_step._cache_size()
    for _ in range(2):
        drv.step()
        assert [x.sharding for x in drv.state] == placed, (d, drv.rounds)
    assert spmd_round_step._cache_size() == before + 1, d
print("placed")
"""


def test_driver_keeps_the_placed_layout():
    """On 1 and 4 of four CPU devices, two ``step()`` calls leave every
    state field in ``place_state``'s layout, and the round is traced once:
    round k's output is round k+1's input as it stands."""
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _LAYOUT_CHECK], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.split()[-1] == "placed"


class _CountedRead:
    """A device scalar of the round state whose host reads are counted."""

    def __init__(self, x, reads):
        self.x, self.reads = x, reads

    def __int__(self):
        self.reads.append(1)
        return int(self.x)

    def block_until_ready(self):
        self.x.block_until_ready()
        return self


def test_sync_new_edges_sum_to_m_without_a_new_read(monkeypatch):
    """Over one whole job the ``sync_new_edges`` counter, one sample a
    round, sums to m: it is the drop in the ``remaining`` the driver reads
    for ``done`` anyway, so a traced job reads the round's scalars
    (``remaining``, ``rounds``) to the host no more often than an untraced
    one."""
    import repro.runtime.driver as driver
    from repro.obs import trace as obs

    reads = []
    step = driver.spmd_round_step

    def counted(*args):
        *head, state = args
        state = state._replace(
            remaining=getattr(state.remaining, "x", state.remaining),
            rounds=getattr(state.rounds, "x", state.rounds))
        out = step(*head, state)
        return out._replace(remaining=_CountedRead(out.remaining, reads),
                            rounds=_CountedRead(out.rounds, reads))

    monkeypatch.setattr(driver, "spmd_round_step", counted)
    g = rmat(10, 8, seed=2)
    cfg = NEConfig(num_partitions=4, k_sel=32, edge_chunk=1 << 10)

    def job():
        reads.clear()
        drv = PartitionDriver(g, cfg, num_devices=1)
        while not drv.done:
            drv.step()
        return drv, len(reads)

    obs.disable()
    _, untraced = job()
    tr = obs.configure()
    try:
        drv, traced = job()
    finally:
        obs.disable()
    assert drv.rounds > 1 and traced == untraced
    new = [e["value"] for e in tr.events
           if e["ev"] == "counter" and e["name"] == "sync_new_edges"]
    assert len(new) == drv.rounds and min(new) >= 0
    assert sum(new) == drv.m


def test_driver_single_mode_matches_partition(graph12):
    """``partition`` is the one-device driver's program run whole."""
    drv = PartitionDriver(graph12, CFG, num_devices=1)
    res = drv.run()
    ref = partition(graph12, CFG)
    np.testing.assert_array_equal(res.edge_part, ref.edge_part)
    np.testing.assert_array_equal(res.vparts, ref.vparts)
    assert res.rounds == ref.rounds


# ---------------------------------------------------------------------------
# kill-at-round-k + resume bit-identity (ISSUE acceptance criterion)
# ---------------------------------------------------------------------------

def test_resume_bit_identity(graph12, snapped_run):
    """Resume from the round-k snapshot == uninterrupted run, bit for bit:
    identical vparts, edge assignment, and replication factor."""
    _, res, snap_dir = snapped_run
    n = graph12.num_vertices
    for k in (1, res.rounds // 2, res.rounds - 1):
        drv = PartitionDriver.resume(graph12, CFG, snap_dir, round_k=k)
        assert drv.rounds == k
        got = drv.run()
        np.testing.assert_array_equal(got.edge_part, res.edge_part)
        np.testing.assert_array_equal(got.vparts, res.vparts)
        st_got = evaluate(np.asarray(graph12.edges), got.edge_part, n,
                          CFG.num_partitions)
        st_ref = evaluate(np.asarray(graph12.edges), res.edge_part, n,
                          CFG.num_partitions)
        assert st_got.replication_factor == st_ref.replication_factor


def test_resume_latest_snapshot(graph12, snapped_run):
    """Default resume picks the newest snapshot — the post-kill path."""
    _, res, snap_dir = snapped_run
    drv = PartitionDriver.resume(graph12, CFG, snap_dir)
    assert drv.rounds == res.rounds
    got = drv.run()        # already at the fixed point: finalize only
    np.testing.assert_array_equal(got.edge_part, res.edge_part)


def test_resume_single_mode(tmp_path):
    """A one-device run over a Graph resumes bit-identically."""
    g = rmat(9, 8, seed=5)
    cfg = NEConfig(num_partitions=4, seed=1, k_sel=32, edge_chunk=1 << 10)
    full = PartitionDriver(g, cfg, num_devices=1, snapshot_dir=tmp_path,
                           snapshot_every=2, keep=100_000).run()
    drv = PartitionDriver.resume(g, cfg, tmp_path, num_devices=1)
    assert drv.rounds > 0
    got = drv.run()
    np.testing.assert_array_equal(got.edge_part, full.edge_part)
    np.testing.assert_array_equal(got.vparts, full.vparts)


def test_resume_wrong_config_fails(graph12, snapped_run):
    """A resume against a different NEConfig must fail loudly."""
    _, _, snap_dir = snapped_run
    other = NEConfig(num_partitions=8, seed=1, k_sel=64, edge_chunk=1 << 12)
    with pytest.raises(SnapshotMismatch):
        PartitionDriver.resume(graph12, other, snap_dir)


def test_resume_wrong_graph_fails(snapped_run):
    """A resume against a different edge source must fail loudly."""
    _, _, snap_dir = snapped_run
    other = rmat(SCALE, 8, seed=4)
    with pytest.raises(SnapshotMismatch):
        PartitionDriver.resume(other, CFG, snap_dir)


def test_resume_wrong_mode_fails(graph12, snapped_run):
    """An spmd snapshot resumed as a hybrid run is refused."""
    from repro.core.hybrid import HybridConfig

    _, _, snap_dir = snapped_run
    hy = HybridConfig(num_partitions=8, budget_frac=1.0, seed=0, k_sel=64,
                      edge_chunk=1 << 12)
    with pytest.raises(SnapshotMismatch):
        PartitionDriver.resume(graph12, hy, snap_dir, mode="hybrid")


def test_fingerprints_discriminate(graph12):
    import dataclasses

    assert config_fingerprint(CFG) == config_fingerprint(CFG)
    assert config_fingerprint(CFG) != config_fingerprint(
        dataclasses.replace(CFG, seed=7))
    assert config_fingerprint(CFG) != config_fingerprint(
        dataclasses.replace(CFG, alpha=1.2))
    assert graph_fingerprint(graph12) == graph_fingerprint(graph12)
    assert graph_fingerprint(graph12) != graph_fingerprint(
        rmat(SCALE, 8, seed=4))


# ---------------------------------------------------------------------------
# artifact store
# ---------------------------------------------------------------------------

def test_artifact_roundtrip(graph12, snapped_run, tmp_path):
    """partition → save_artifact → load_artifact → identical edge_part /
    replica map (the PartitionResult serialization satellite)."""
    drv, res, _ = snapped_run
    art = drv.save_artifact(tmp_path / "art")
    loaded = load_artifact(tmp_path / "art")
    np.testing.assert_array_equal(loaded.edge_part, res.edge_part)
    np.testing.assert_array_equal(loaded.vparts, res.vparts)
    np.testing.assert_array_equal(loaded.edges_per_part, res.edges_per_part)
    np.testing.assert_array_equal(loaded.edges, np.asarray(graph12.edges))
    back = loaded.result()
    np.testing.assert_array_equal(back.edge_part, res.edge_part)
    assert back.rounds == res.rounds and back.leftover == res.leftover
    # per-partition shards decode independently and agree with the whole
    for p in (0, CFG.num_partitions - 1):
        e_p = loaded.partition_edges(p)
        np.testing.assert_array_equal(
            e_p, np.asarray(graph12.edges)[res.edge_part == p])
        assert e_p.shape[0] == int(res.edges_per_part[p])
    # compression actually compresses (vs 8 B/edge raw + bitmap)
    part_bytes = sum((loaded.dir / f"part_{p:05d}.bin").stat().st_size
                     for p in range(CFG.num_partitions))
    assert part_bytes < 8 * graph12.num_edges


def test_artifact_feeds_gas_engine(graph12, snapped_run, tmp_path):
    """The loaded artifact builds the identical vertex-cut engine structure
    the in-memory result builds — no re-partitioning."""
    from repro.apps.engine import build_sharded_graph

    drv, res, _ = snapped_run
    drv.save_artifact(tmp_path / "art")
    loaded = load_artifact(tmp_path / "art")
    sg_art = loaded.sharded_graph(CFG.num_partitions)
    sg_ref = build_sharded_graph(np.asarray(graph12.edges), res.edge_part,
                                 graph12.num_vertices, CFG.num_partitions)
    for field in ("edges_ml", "emask", "mirror_glob", "mirror_mask",
                  "send_idx", "send_mask", "recv_owned", "owned_glob",
                  "owned_mask"):
        np.testing.assert_array_equal(getattr(sg_art, field),
                                      getattr(sg_ref, field))
    assert sg_art.comm_slots == sg_ref.comm_slots


def test_artifact_rejects_incomplete_assignment(tmp_path):
    from repro.core.partitioner import PartitionResult

    res = PartitionResult(np.array([0, -1], np.int32), np.zeros((3, 2), bool),
                          np.array([1, 0], np.int32), 1, 0)
    with pytest.raises(ValueError, match="complete assignment"):
        save_artifact(tmp_path / "a", res,
                      np.array([[0, 1], [1, 2]], np.int32), 3)


def test_artifact_checksum_detects_corruption(graph12, snapped_run, tmp_path):
    drv, _, _ = snapped_run
    drv.save_artifact(tmp_path / "art")
    loaded = load_artifact(tmp_path / "art")
    path = loaded.dir / "part_00000.bin"
    raw = bytearray(path.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    path.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="checksum"):
        load_artifact(tmp_path / "art").partition_edges(0)


# ---------------------------------------------------------------------------
# multi-host ingestion
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def store_file(tmp_path_factory):
    import repro.io as rio

    td = tmp_path_factory.mktemp("store")
    return rio.spill_canonical_rmat(td, 10, 8, seed=3, chunk_size=1 << 10)


def test_host_block_ranges_tile_and_balance(store_file):
    for hosts in (1, 2, 3, 7):
        ranges = host_block_ranges(store_file, hosts)
        assert len(ranges) == hosts
        assert ranges[0][0] == 0 and ranges[-1][1] == store_file.num_blocks
        for (a, b), (c, _) in zip(ranges, ranges[1:]):
            assert b == c and a <= b
        covered = sum(store_file.edges_in_blocks(a, b) for a, b in ranges)
        assert covered == store_file.num_edges


@pytest.mark.parametrize("hosts", [1, 2, 3])
def test_ingest_matches_shard_edges_stream(store_file, hosts):
    """Multi-host assembly is bit-identical to the sequential pass — the
    partitioner cannot tell how many hosts fed it."""
    ref = shard_edges_stream(store_file, 4, with_edges=True)
    got = ingest_edgefile(store_file, 4, num_hosts=hosts, with_edges=True)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


def test_cluster_importable_without_jax(store_file):
    """The ingestion workers must stay lightweight: unpickling
    ``cluster._ingest_worker`` in a spawn worker imports
    ``repro.runtime.cluster`` through the package __init__, and that path
    must not drag jax (or the driver) into every worker process."""
    import subprocess
    import sys

    code = ("import sys; import repro.runtime.cluster; "
            "assert 'jax' not in sys.modules, 'cluster import pulled jax'; "
            "assert 'repro.runtime.driver' not in sys.modules")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_ingest_process_pool(store_file):
    ref = shard_edges_stream(store_file, 4)
    got = ingest_edgefile(store_file, 4, num_hosts=2, processes=True)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


def test_driver_from_store(store_file):
    """The EdgeFile front door: ingest by host ranges, partition, and match
    the fire-and-forget store path."""
    cfg = NEConfig(num_partitions=4, seed=0, k_sel=64, edge_chunk=1 << 12)
    res = PartitionDriver(store_file, cfg, num_hosts=2).run()
    ref = partition_spmd(store_file, cfg)
    np.testing.assert_array_equal(res.edge_part, ref.edge_part)
    np.testing.assert_array_equal(res.vparts, ref.vparts)


def test_edgefile_block_range_reads(store_file):
    full = store_file.read_all()
    a = store_file.read_blocks(0, 2)
    b = store_file.read_blocks(2)
    np.testing.assert_array_equal(np.concatenate([a, b]), full)
    assert store_file.edges_in_blocks(0, 2) == a.shape[0]
    assert store_file.edges_in_blocks() == store_file.num_edges
    assert store_file.read_blocks(5, 5).shape == (0, 2)
    assert list(store_file.iter_blocks(1, 1)) == []


# ---------------------------------------------------------------------------
# sharded checkpoint manager
# ---------------------------------------------------------------------------

def test_sharded_checkpoint_roundtrip(tmp_path):
    mgr = ShardedCheckpointManager(tmp_path, keep=2)
    rep = {"counts": np.arange(8, dtype=np.int32)}
    sharded = {"edge_part": np.arange(24, dtype=np.int32).reshape(4, 6)}
    mgr.save(3, rep, sharded=sharded, extra_meta={"mode": "spmd"})
    # per-shard files exist — the unit a multi-host deployment writes/reads
    files = sorted(p.name for p in mgr._step_dir(3).iterdir())
    assert [f for f in files if f.startswith("edge_part.shard")] == [
        f"edge_part.shard{i:05d}.bin" for i in range(4)]
    np.testing.assert_array_equal(mgr.load_shard(3, "edge_part", 2),
                                  sharded["edge_part"][2])
    np.testing.assert_array_equal(mgr.load_sharded(3, "edge_part"),
                                  sharded["edge_part"])
    assert mgr.meta(3) == {"mode": "spmd"}
    assert mgr.shard_names(3) == ["edge_part"]


def test_sharded_checkpoint_shard_corruption(tmp_path):
    mgr = ShardedCheckpointManager(tmp_path)
    mgr.save(1, {}, sharded={"x": np.ones((2, 3), np.float32)})
    (mgr._step_dir(1) / "x.shard00001.bin").write_bytes(b"\0" * 12)
    np.testing.assert_array_equal(mgr.load_shard(1, "x", 0), np.ones(3))
    with pytest.raises(IOError, match="checksum"):
        mgr.load_shard(1, "x", 1)


def test_run_snapshot_skips_half_written(tmp_path, graph12):
    """A torn newest snapshot falls back to the previous round; a valid
    snapshot of the wrong run raises instead of falling back."""
    snap = RunSnapshot(tmp_path, CFG, graph_fingerprint(graph12))
    fields = {"edge_part": np.zeros((2, 4), np.int32),
              "vparts": np.zeros((5, 8), bool),
              "rounds": np.int32(1)}
    snap.save_state(1, fields, "spmd")
    fields["rounds"] = np.int32(2)
    snap.save_state(2, fields, "spmd")
    # tear round 2: truncate a shard file after publication
    (snap.mgr._step_dir(2) / "edge_part.shard00001.bin").write_bytes(b"xy")
    got, rnd, mode = snap.restore_state()
    assert rnd == 1 and mode == "spmd"
    np.testing.assert_array_equal(got["edge_part"], fields["edge_part"])


# ---------------------------------------------------------------------------
# multi-writer snapshot protocol (repro.runtime.multihost)
# ---------------------------------------------------------------------------

def _multiwriter_save(snap, round_k, fields, ep, hosts=2):
    """Replay the cooperative protocol single-process, in protocol order:
    host 0 drives save_state_multihost, and the other hosts' shard writes
    happen at the all-shards barrier — exactly where they land in a real
    multi-process run (after begin_shared, before publish_shared)."""
    d = ep.shape[0]
    per_host = d // hosts

    def slices(h):
        return {i: ep[i] for i in range(h * per_host, (h + 1) * per_host)}

    def barrier(name):
        if name == f"snap-shards-{round_k}":
            for h in range(1, hosts):
                snap.mgr.write_host_shards(round_k, h,
                                           {"edge_part": slices(h)})

    snap.save_state_multihost(round_k, fields, "spmd", 0,
                              {"edge_part": slices(0)}, {"edge_part": d},
                              barrier)


def test_multiwriter_layout_matches_single_writer(tmp_path, graph12):
    """A cooperatively-written step restores byte-identically to a
    single-writer step — cross process-count resume compatibility."""
    fp = graph_fingerprint(graph12)
    ep = np.arange(32, dtype=np.int32).reshape(8, 4)
    fields = {"vparts": np.ones((6, 8), bool), "rounds": np.int32(5)}
    single = RunSnapshot(tmp_path / "s1", CFG, fp)
    single.save_state(5, dict(fields, edge_part=ep), "spmd")
    multi = RunSnapshot(tmp_path / "s2", CFG, fp)
    _multiwriter_save(multi, 5, fields, ep)
    f1, r1, m1 = single.restore_state()
    f2, r2, m2 = multi.restore_state()
    assert (r1, m1) == (r2, m2) == (5, "spmd")
    for k in f1:
        np.testing.assert_array_equal(f1[k], f2[k])


def test_multiwriter_unpublished_staging_is_invisible(tmp_path, graph12):
    """A kill between shard staging and publish leaves only a dot-prefixed
    tmp dir: the round is not listed, restore falls back, and the next
    save of that round reclaims the staging."""
    snap = RunSnapshot(tmp_path, CFG, graph_fingerprint(graph12))
    ep = np.zeros((4, 3), np.int32)
    fields = {"rounds": np.int32(1)}
    _multiwriter_save(snap, 1, fields, ep)
    # round 2 dies after host 0 staged its shards — no publish
    meta = {"mode": "spmd", "round": 2, "config_fingerprint": snap.cfg_fp,
            "graph_fingerprint": snap.graph_fp}
    snap.mgr.begin_shared(2, {"rounds": np.int32(2)}, extra_meta=meta)
    snap.mgr.write_host_shards(2, 0, {"edge_part": {0: ep[0], 1: ep[1]}})
    assert snap.rounds() == [1]
    _, rnd, _, _ = snap.restore_state_multihost([0, 1])
    assert rnd == 1
    # the next save of round 2 reclaims the leftover staging dir
    _multiwriter_save(snap, 2, {"rounds": np.int32(2)}, ep)
    assert snap.rounds() == [1, 2]
    assert not snap.mgr.shared_tmp(2).exists()


def test_multiwriter_refuses_missing_host_slices(tmp_path, graph12):
    """publish_shared fails loudly if any global shard index was never
    staged — a torn step must not become the newest published round."""
    snap = RunSnapshot(tmp_path, CFG, graph_fingerprint(graph12))
    meta = {"mode": "spmd", "round": 1, "config_fingerprint": snap.cfg_fp,
            "graph_fingerprint": snap.graph_fp}
    snap.mgr.begin_shared(1, {"rounds": np.int32(1)}, extra_meta=meta)
    snap.mgr.write_host_shards(1, 0, {"edge_part": {0: np.zeros(3)}})
    with pytest.raises(IOError, match="no host staged"):
        snap.mgr.publish_shared(1, {"edge_part": 4})
    assert snap.rounds() == []


def test_restore_multihost_loads_owned_slices_only(tmp_path, graph12):
    snap = RunSnapshot(tmp_path, CFG, graph_fingerprint(graph12))
    ep = np.arange(20, dtype=np.int32).reshape(4, 5)
    _multiwriter_save(snap, 3, {"rounds": np.int32(3)}, ep)
    fields, rnd, mode, counts = snap.restore_state_multihost([1, 3])
    assert (rnd, mode, counts) == (3, "spmd", {"edge_part": 4})
    assert sorted(fields["edge_part"]) == [1, 3]
    np.testing.assert_array_equal(fields["edge_part"][3], ep[3])


# ---------------------------------------------------------------------------
# exchange-dir ingestion (true multi-controller path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hosts", [1, 2, 3])
def test_exchange_ingestion_bit_identical(store_file, tmp_path, hosts):
    """Spill-per-host + assemble-owned == the sequential 2D-hash pass:
    the round program cannot tell which process fed each shard."""
    from repro.runtime.cluster import (exchange_assemble,
                                       exchange_read_global,
                                       exchange_write_range)

    ref_sh, ref_mk, ref_cap, ref_dev, ref_edges = shard_edges_stream(
        store_file, 4, with_edges=True)
    ex = tmp_path / "exchange"
    for h in range(hosts):
        exchange_write_range(ex, store_file.path, h, hosts, 4)
    shards, masks, cap, degree = exchange_assemble(ex, hosts, 4, [0, 2, 3])
    assert cap == ref_cap
    for d in (0, 2, 3):
        np.testing.assert_array_equal(shards[d], ref_sh[d])
        np.testing.assert_array_equal(masks[d], ref_mk[d])
    edges, dev = exchange_read_global(ex, hosts)
    np.testing.assert_array_equal(edges, ref_edges)
    np.testing.assert_array_equal(dev, ref_dev)
    deg = np.zeros(int(store_file.num_vertices), np.int64)
    np.add.at(deg, ref_edges[:, 0], 1)
    np.add.at(deg, ref_edges[:, 1], 1)
    np.testing.assert_array_equal(degree, deg)


# ---------------------------------------------------------------------------
# sharded finalize epilogue (repro.core.epilogue + repro.runtime.finalize)
# ---------------------------------------------------------------------------

def _fabricated_layout(seed=0, n=400, m=3000, p_num=8, num_devices=4,
                       leftover_frac=0.1):
    """A deterministic partial assignment over a 2D-hash shard layout —
    the raw material of a finalize epilogue, without running a
    partitioner."""
    from repro.io.csr import grid_assign_host

    rng = np.random.default_rng(seed)
    edges = rng.integers(0, n, size=(m, 2)).astype(np.int32)
    dev = grid_assign_host(edges, num_devices)
    eids = {d: np.flatnonzero(dev == d).astype(np.int64)
            for d in range(num_devices)}
    ep = ((edges[:, 0].astype(np.int64) * 31 + edges[:, 1])
          % p_num).astype(np.int32)
    ep[rng.random(m) < leftover_frac] = -1
    vparts = np.zeros((n, p_num), bool)
    ok = ep >= 0
    vparts[edges[ok, 0], ep[ok]] = True
    vparts[edges[ok, 1], ep[ok]] = True
    counts = np.bincount(ep[ok], minlength=p_num).astype(np.int32)
    return edges, dev, eids, ep, vparts, counts


def test_leftover_plan_matches_cleanup():
    """leftover_plan + leftover_targets reproduce the pre-split
    cleanup_leftovers water-fill exactly (including the overflow case)."""
    from repro.core.epilogue import (alpha_limit, cleanup_leftovers,
                                     leftover_plan, leftover_targets)

    rng = np.random.default_rng(7)
    for _ in range(20):
        p_num = int(rng.integers(2, 9))
        counts = rng.integers(0, 50, size=p_num).astype(np.int32)
        k = int(rng.integers(0, 200))
        limit = alpha_limit(1.1, int(counts.sum()) + k, p_num)
        take = leftover_plan(counts, k, p_num, limit)
        assert int(take.sum()) == k
        ref = np.repeat(np.arange(p_num, dtype=np.int32), take)
        got = leftover_targets(take, np.arange(k))
        np.testing.assert_array_equal(ref, got)
        # capacity respected while any partition has room
        if k <= int(np.maximum(limit - counts.astype(np.int64), 0).sum()):
            assert ((counts + take) <= max(limit, int(counts.max()))).all()
        # and the composed single-host path still agrees with itself
        ep = np.concatenate([np.zeros(int(counts.sum()), np.int32),
                             np.full(k, -1, np.int32)])
        ep[:int(counts.sum())] = np.repeat(
            np.arange(p_num, dtype=np.int32), counts)
        edges = np.zeros((ep.size, 2), np.int64)
        vp = np.zeros((1, p_num), bool)
        c2 = counts.copy()
        assert cleanup_leftovers(ep, vp, c2, edges, p_num, limit) == k
        np.testing.assert_array_equal(c2, counts + take)


def test_sharded_finalize_bit_identical_and_bounded():
    """The per-host epilogue (stage → rank → slice-local apply → OR/sum
    combine) reproduces the whole-array finalize bit for bit, and no
    per-host structure it touches is O(m) — the allocation-shape half of
    the 'no global edge_part' acceptance criterion."""
    from repro.core.epilogue import (alpha_limit, cleanup_leftovers,
                                     stitch_slices)
    from repro.core.metrics import stats_from_counts
    from repro.runtime import finalize as fz

    n, m, p_num, num_devices, hosts = 400, 3000, 8, 4, 2
    edges, dev, eids, ep_full, vparts, counts = _fabricated_layout(
        n=n, m=m, p_num=p_num, num_devices=num_devices)
    limit = alpha_limit(1.1, m, p_num)

    ref_ep, ref_vp, ref_counts = ep_full.copy(), vparts.copy(), counts.copy()
    leftover = cleanup_leftovers(ref_ep, ref_vp, ref_counts, edges,
                                 p_num, limit)
    assert leftover > 0                      # the fixture must exercise it

    owned = {0: [0, 1], 1: [2, 3]}
    slices = {d: ep_full[eids[d]].copy() for d in range(num_devices)}
    us = {d: edges[eids[d], 0] for d in range(num_devices)}
    vs = {d: edges[eids[d], 1] for d in range(num_devices)}
    max_slice = max(e.size for e in eids.values())

    fin = None
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        fin = os.path.join(td, "fin")
        staged = {}
        for h in range(hosts):
            staged[h] = fz.stage_leftovers(
                fin, h, {d: slices[d] for d in owned[h]},
                {d: eids[d] for d in owned[h]})
            # per-host leftover spill is O(own leftovers), not O(m)
            assert staged[h].size < m
        vp_host, takes = {}, {}
        for h in range(hosts):
            vp_host[h] = vparts.copy()
            takes[h], total = fz.apply_leftovers(
                fin, h, hosts, staged[h],
                {d: slices[d] for d in owned[h]},
                {d: us[d] for d in owned[h]},
                {d: vs[d] for d in owned[h]},
                {d: eids[d] for d in owned[h]},
                counts, limit, p_num, vp_host[h])
        np.testing.assert_array_equal(takes[0], takes[1])
        assert total == leftover
        # the combine step is (P,)- and (N,P)-sized, never (m,)
        vp_comb = vp_host[0] | vp_host[1]
        counts_after = (counts.astype(np.int64) + takes[0]).astype(np.int32)
        stats = stats_from_counts(vp_comb.sum(axis=0), counts_after, n)

        # every per-host array is bounded by its slices
        for d in range(num_devices):
            assert slices[d].shape == (eids[d].size,)
            assert eids[d].size <= max_slice < m

        out = np.full(m, -1, np.int32)
        stitch_slices(out, slices, eids)
        np.testing.assert_array_equal(out, ref_ep)
        np.testing.assert_array_equal(vp_comb, ref_vp)
        np.testing.assert_array_equal(counts_after, ref_counts)
        assert stats.replicas_total == int(ref_vp.sum())

        # contributions for the multi-writer artifact stay slice-bounded
        for h in range(hosts):
            contribs = fz.partition_contribs(
                {d: slices[d] for d in owned[h]},
                {d: us[d] for d in owned[h]},
                {d: vs[d] for d in owned[h]},
                {d: eids[d] for d in owned[h]}, p_num)
            assert sum(c[0].size for c in contribs.values()) \
                == sum(eids[d].size for d in owned[h])

        # lazy materialization path agrees too
        le, lt = fz.leftover_assignments(fin, hosts, takes[0])
        chk = ep_full.copy()
        chk[le] = lt
        np.testing.assert_array_equal(chk, ref_ep)


def test_multiwriter_artifact_bit_identical(tmp_path):
    """A cooperatively-written artifact (per-host contributions, owner
    encode, writer-0 publish) is byte-identical to the single-writer
    save_artifact: same files, same checksums, same manifest bytes."""
    import types

    from repro.runtime import artifact as art
    from repro.runtime import finalize as fz

    n, m, p_num, num_devices, hosts = 400, 3000, 8, 4, 2
    edges, dev, eids, ep, vparts, counts = _fabricated_layout(
        n=n, m=m, p_num=p_num, num_devices=num_devices, leftover_frac=0.0)
    res = types.SimpleNamespace(edge_part=ep, vparts=vparts,
                                edges_per_part=counts, rounds=9, leftover=0)
    art.save_artifact(tmp_path / "ref", res, edges, n,
                      config_fingerprint="cfg", graph_fingerprint="g")

    owned = {0: [0, 1], 1: [2, 3]}
    slices = {d: ep[eids[d]] for d in range(num_devices)}
    art.begin_shared_artifact(tmp_path / "mw")
    for h in range(hosts):
        contribs = fz.partition_contribs(
            {d: slices[d] for d in owned[h]},
            {d: edges[eids[d], 0] for d in owned[h]},
            {d: edges[eids[d], 1] for d in owned[h]},
            {d: eids[d] for d in owned[h]}, p_num)
        art.write_artifact_contrib(tmp_path / "mw", h, contribs)
    for h in range(hosts):
        art.encode_shared_parts(tmp_path / "mw", h,
                                list(range(h, p_num, hosts)), hosts)
    art.publish_shared_artifact(
        tmp_path / "mw", num_vertices=n, num_edges=m,
        num_partitions=p_num, num_hosts=hosts, vparts=vparts,
        edges_per_part=counts, rounds=9, leftover=0,
        config_fingerprint="cfg", graph_fingerprint="g")

    ref_files = sorted(p.name for p in (tmp_path / "ref").iterdir())
    mw_files = sorted(p.name for p in (tmp_path / "mw").iterdir())
    assert ref_files == mw_files
    for name in ref_files:
        assert (tmp_path / "ref" / name).read_bytes() \
            == (tmp_path / "mw" / name).read_bytes(), name
    loaded = load_artifact(tmp_path / "mw")
    np.testing.assert_array_equal(loaded.edge_part, ep)


def test_multiwriter_artifact_torn_save_invisible(tmp_path):
    """A writer killed anywhere before publish leaves only the
    dot-prefixed staging dir; a pre-existing artifact at the target stays
    intact; publish refuses partitions nobody encoded."""
    import types

    from repro.runtime import artifact as art
    from repro.runtime import finalize as fz

    n, m, p_num, num_devices = 300, 2000, 4, 2
    edges, dev, eids, ep, vparts, counts = _fabricated_layout(
        n=n, m=m, p_num=p_num, num_devices=num_devices, leftover_frac=0.0)
    res = types.SimpleNamespace(edge_part=ep, vparts=vparts,
                                edges_per_part=counts, rounds=3, leftover=0)
    target = tmp_path / "art"
    art.save_artifact(target, res, edges, n)
    before = {p.name: p.read_bytes() for p in target.iterdir()}

    # second save dies after host 0's contribution — never published
    art.begin_shared_artifact(target)
    contribs = fz.partition_contribs(
        {0: ep[eids[0]]}, {0: edges[eids[0], 0]}, {0: edges[eids[0], 1]},
        {0: eids[0]}, p_num)
    art.write_artifact_contrib(target, 0, contribs)
    after = {p.name: p.read_bytes() for p in target.iterdir()}
    assert before == after                      # old artifact untouched
    assert art._shared_tmp(target).exists()     # only dot-prefixed staging

    # host 1 never contributed → encode of its merge fails loudly
    with pytest.raises(IOError, match="never staged"):
        art.encode_shared_parts(target, 0, [0], num_hosts=2)
    # and publish refuses partitions nobody encoded
    with pytest.raises(IOError, match="no host encoded"):
        art.publish_shared_artifact(
            target, num_vertices=n, num_edges=m, num_partitions=p_num,
            num_hosts=2, vparts=vparts, edges_per_part=counts, rounds=3,
            leftover=0)
    # the next cooperative save reclaims the torn staging
    art.begin_shared_artifact(target)
    for h, own in ((0, [0]), (1, [1])):
        art.write_artifact_contrib(target, h, fz.partition_contribs(
            {d: ep[eids[d]] for d in own}, {d: edges[eids[d], 0] for d in own},
            {d: edges[eids[d], 1] for d in own}, {d: eids[d] for d in own},
            p_num))
    for h in (0, 1):
        art.encode_shared_parts(target, h, list(range(h, p_num, 2)), 2)
    art.publish_shared_artifact(
        target, num_vertices=n, num_edges=m, num_partitions=p_num,
        num_hosts=2, vparts=vparts, edges_per_part=counts, rounds=3,
        leftover=0)
    assert not art._shared_tmp(target).exists()
    np.testing.assert_array_equal(load_artifact(target).edge_part, ep)


def test_reshard_stream_matches_memory(store_file, tmp_path):
    """The store-backed elastic reshard (reshard_write/reshard_assemble)
    moves per-edge values onto a new device count identically to the
    in-memory stitch + re-split, with every process holding only its
    balanced share."""
    from repro.dist.partitioner_sm import stitch_edge_part
    from repro.io.csr import grid_assign_host
    from repro.runtime.cluster import (exchange_write_range,
                                       reshard_assemble, reshard_write)

    hosts, d_old, d_new = 2, 4, 2
    ref_sh, _, _, dev_old, edges = shard_edges_stream(store_file, d_old,
                                                      with_edges=True)
    m = int(store_file.num_edges)
    # fabricated old assignment values: distinguishable per edge
    old_full = (np.arange(m) % 7 - 1).astype(np.int32)
    old_slices = {d: np.full(ref_sh.shape[1], -1, np.int32)
                  for d in range(d_old)}
    for d in range(d_old):
        sel = np.flatnonzero(dev_old == d)
        old_slices[d][:sel.size] = old_full[sel]

    # exchange spills for the NEW layout (what a resumed driver writes)
    ex = tmp_path / "exchange"
    for h in range(hosts):
        exchange_write_range(ex, store_file.path, h, hosts, d_new)
    dev_new = grid_assign_host(edges, d_new)

    spill = tmp_path / "reshard"
    for h in range(hosts):
        mine = {i: old_slices[i] for i in range(d_old) if i % hosts == h}
        reshard_write(spill, ex, hosts, mine, d_old, d_new, h)
    got = {}
    for h in range(hosts):
        owned = [d for d in range(d_new) if d % hosts == h]
        cap_new = int(np.bincount(dev_new, minlength=d_new).max())
        got.update(reshard_assemble(spill, hosts, owned, cap_new))

    # reference: stitch the old layout to edge order, re-split by new dev
    full = stitch_edge_part(np.stack([old_slices[d] for d in range(d_old)]),
                            dev_old, m)
    np.testing.assert_array_equal(full, old_full)
    for d in range(d_new):
        sel = np.flatnonzero(dev_new == d)
        np.testing.assert_array_equal(got[d][:sel.size], full[sel])
        assert (got[d][sel.size:] == -1).all()


def test_elastic_restore_reshards_in_memory(tmp_path):
    """A single-controller spmd driver restores snapshots taken on a
    different device count: the slices reshard (preserving every per-edge
    value) and the run completes with a valid partition."""
    g = rmat(9, 8, seed=5)
    cfg = NEConfig(num_partitions=4, seed=1, k_sel=32, edge_chunk=1 << 10)
    drv8 = PartitionDriver(g, cfg, num_devices=8, snapshot_dir=tmp_path,
                           snapshot_every=1, keep=100_000)
    res8 = drv8.run()

    # resume at the fixed point on 4 devices: values preserved exactly,
    # so the finalized result is identical
    drv4 = PartitionDriver.resume(g, cfg, tmp_path, num_devices=4)
    assert drv4.rounds == res8.rounds
    res4 = drv4.run()
    np.testing.assert_array_equal(res4.edge_part, res8.edge_part)
    np.testing.assert_array_equal(res4.vparts, res8.vparts)

    # resume mid-run on 4 devices: a valid complete partition comes out
    k = max(res8.rounds // 2, 1)
    drv4b = PartitionDriver.resume(g, cfg, tmp_path, num_devices=4,
                                   round_k=k)
    assert drv4b.rounds == k
    got = drv4b.run()
    ep = got.edge_part
    assert (ep >= 0).all()
    np.testing.assert_array_equal(
        np.bincount(ep, minlength=4), got.edges_per_part)


def test_epilogue_importable_without_jax():
    """The whole sharded-epilogue path — core.epilogue, runtime.finalize,
    runtime.artifact, runtime.cluster — must import jax-free: the
    bench_memory finalize-RSS children depend on it (and it proves no
    epilogue step leans on device arrays)."""
    import subprocess
    import sys

    code = ("import sys; "
            "import repro.core.epilogue, repro.core.metrics, "
            "repro.runtime.finalize, repro.runtime.artifact, "
            "repro.runtime.cluster, repro.io.atomicdir; "
            "assert 'jax' not in sys.modules, 'epilogue path pulled jax'")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_finalize_attaches_stats(graph12, snapped_run):
    """Every finalize path computes PartitionStats from the (P,)-sized
    count partials, matching evaluate() of the full assignment."""
    _, res, _ = snapped_run
    assert res.stats is not None
    ref = evaluate(np.asarray(graph12.edges), res.edge_part,
                   graph12.num_vertices, CFG.num_partitions)
    assert res.stats.replication_factor == ref.replication_factor
    assert res.stats.edge_balance == ref.edge_balance
    assert res.stats.replicas_total == ref.replicas_total


def test_lazy_partition_result_materializes_once():
    from repro.core.partitioner import PartitionResult

    calls = []

    def make():
        calls.append(1)
        return np.arange(5, dtype=np.int32)

    res = PartitionResult(make, None, None, 1, 0)
    assert not res.edge_part_materialized
    np.testing.assert_array_equal(res.edge_part, np.arange(5))
    assert res.edge_part_materialized
    np.testing.assert_array_equal(res.edge_part, np.arange(5))
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# one process per chip + compile cache placement
# ---------------------------------------------------------------------------

def test_child_env_inherits_platform(monkeypatch):
    """Workers inherit the platform: the launcher never defaults it."""
    from repro.runtime.multihost import child_env

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert "JAX_PLATFORMS" not in child_env(4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    env = child_env(4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]


@pytest.mark.parametrize("platforms", [None, "tpu", "tpu,cpu"])
def test_launch_local_refuses_gang_sharing_chips(monkeypatch, platforms):
    """Several local workers run only pinned to the CPU; nothing spawns."""
    import sys

    from repro.runtime.multihost import launch_local

    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(ValueError, match="share this host's chips"):
        launch_local([sys.executable, "-c", "raise SystemExit(3)"],
                     num_processes=2, devices_per_process=1)


def test_compile_cache_placement(monkeypatch):
    """Unset: the fixed checkout-root ``.jax_cache``; set: JAX's own
    reading of ``JAX_COMPILATION_CACHE_DIR`` is left alone."""
    import jax

    from repro import compile_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() is None
        monkeypatch.delenv(compile_cache.ENV_VAR)
        assert compile_cache.enable() == os.path.join(root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
