"""Round-level state machine over Distributed NE — pause, snapshot, resume.

``partition`` / ``partition_spmd`` are fire-and-forget: one jit call runs
every round inside a ``while_loop`` and nothing survives a crash.  The
:class:`PartitionDriver` re-expresses the same computation as a host-driven
state machine — one jit call per paper round, on *exactly the traced round
function the whole-run jits use* (``core.partitioner._round`` /
``dist.partitioner_sm._spmd_round``).  All round state is integer or
counter-mode PRNG, so stepping is bit-identical to the uninterrupted
while_loop, and therefore so is kill-at-round-k + resume-from-snapshot
(asserted by tests/test_runtime.py and the 8-device SPMD checks).

The driver owns the operational envelope the paper's 256-machine runs
presume:

* **ingestion** — a Graph shards in memory; a canonical EdgeFile shards
  through :mod:`repro.runtime.cluster` host block ranges, each range
  streamed and hashed independently (optionally in worker processes).
  Under ``jax.distributed`` (``jax.process_count() > 1``) the driver goes
  truly multi-controller: each process ingests only its own block range
  through the cluster exchange, assembles only the shards of the devices
  it owns, and the round state lives in global ``jax.Array``\\ s spanning
  all processes (see :mod:`repro.runtime.multihost`);
* **snapshots** — every ``snapshot_every`` rounds the round state goes
  through :class:`repro.runtime.snapshot.RunSnapshot` (sharded files,
  fsync + atomic rename, config/graph fingerprints).  Resume against the
  wrong EdgeFile or NEConfig fails loudly;
* **finalize** — single-controller runs stitch shard-order assignments
  back to edge order and run the shared water-filling cleanup; a
  multi-controller run finalizes **sharded**: each host cleans up only
  its owned slices (:mod:`repro.runtime.finalize`), the quality metrics
  combine from (P,)-sized partials via :mod:`repro.dist.compat`
  collectives, the artifact persists through the cooperative multi-writer
  protocol (:mod:`repro.runtime.artifact`), and the returned
  :class:`PartitionResult` carries a *lazy* ``edge_part`` — no host ever
  materializes the O(M) global assignment unless a test or small-graph
  consumer forces it;
* **elastic resume** — restoring onto a different process count at the
  same device count just moves slice ownership; a different *device*
  count reshards the slices through a store-backed exchange
  (:func:`repro.runtime.cluster.reshard_write`) instead of refusing.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph, as_graph, shard_edges
from repro.core.metrics import stats_from_counts
from repro.core.partitioner import (NEConfig, NEState, PartitionResult,
                                    alpha_limit, finalize_result, ne_done,
                                    ne_init_state, ne_round_step)
from repro.dist import compat
from repro.dist.partitioner_sm import (AXIS, SpmdState, place_state,
                                       round_quality,
                                       round_sync_payload_bytes, shard_over,
                                       spmd_init_state, spmd_round_step,
                                       stitch_edge_part, sync_chunk)
from repro.io.edgefile import EdgeFile
from repro.kernels.ne_round import ops as ne_ops
from repro.io.stream import require_canonical
from repro.launch.mesh import make_edge_mesh
from repro.obs import live
from repro.obs import trace as obs
from repro.runtime import cluster
from repro.runtime.artifact import PartitionArtifact, save_artifact
from repro.runtime.snapshot import (RunSnapshot, SnapshotMismatch,
                                    config_fingerprint, graph_fingerprint)


class PartitionDriver:
    """Interruptible, resumable Distributed NE run.

    ``mode="spmd"`` (default) drives the shard_map partitioner over
    ``num_devices``; ``mode="single"`` drives the single-controller
    fixed point; ``mode="hybrid"`` drives the HEP-style hybrid
    (``cfg`` must then be a :class:`repro.core.hybrid.HybridConfig`) —
    the tail is grid-hashed at ingest, rounds step the *same*
    ``ne_round_step`` over the low subgraph from the seeded state, and
    finalize stitches through ``hybrid_finalize``; snapshots/resume
    inherit round-for-round (the seeded state is just an NEState).  One
    :meth:`step` == one paper round; :meth:`run` loops to completion
    with periodic snapshots; :meth:`resume` rebuilds a driver from the
    latest (or a chosen) snapshot.
    """

    def __init__(self, source, cfg: NEConfig, num_devices: int | None = None,
                 mode: str = "spmd", snapshot_dir: str | os.PathLike | None = None,
                 snapshot_every: int = 0, keep: int = 3,
                 num_hosts: int | None = None, ingest_processes: bool = False,
                 exchange_dir: str | os.PathLike | None = None):
        if mode not in ("spmd", "single", "hybrid"):
            raise ValueError(f"unknown mode {mode!r}")
        self.mode = mode
        self.source = source
        self.snapshot_every = int(snapshot_every)
        self._result: PartitionResult | None = None
        self._done: bool | None = None
        # SPMD: ``remaining`` as last read from the device (None until the
        # current state's read), the read before it, and their difference
        self._rem: int | None = None
        self._rem_last: int | None = None
        self._new_edges: int | None = None
        self._host, self._nprocs = compat.process_env()
        self.multihost = self.mode == "spmd" and self._nprocs > 1
        self._final_slices = None   # set by the sharded finalize epilogue
        # test-only crash-injection point for the multi-writer snapshot
        # protocol (see RunSnapshot.save_state_multihost / the kill-at-
        # round-k integration checks); never set in production runs
        self.snapshot_fault_hook = None

        if mode in ("single", "hybrid") and self._nprocs > 1:
            raise ValueError(f"mode={mode!r} is single-controller by "
                             "definition — multi-process runs drive the "
                             "SPMD partitioner (mode='spmd')")
        obs.watch_compiles()
        with obs.span("ingest", cat="runtime", mode=mode):
            if mode == "hybrid":
                from repro.core.hybrid import (HybridConfig,
                                               hybrid_init_state,
                                               hybrid_split)

                if not isinstance(cfg, HybridConfig):
                    raise TypeError("mode='hybrid' takes a HybridConfig, "
                                    f"got {type(cfg).__name__}")
                self._graph_fp = graph_fingerprint(source)
                split = hybrid_split(source, cfg)
                self.cfg = cfg.clamped(split.num_vertices)
                self._necfg = self.cfg.ne_config()
                self._split = split
                self._graph = split.low
                self.n, self.m = split.num_vertices, split.num_edges
                self._edges = None      # materialized lazily by save_artifact
                self.limit = alpha_limit(self.cfg.alpha, self.m,
                                         self.cfg.num_partitions)
                self.state = hybrid_init_state(split, self._necfg)
            elif mode == "single":
                g = source if isinstance(source, EdgeFile) \
                    else as_graph(source)
                self._graph_fp = graph_fingerprint(g)
                g = as_graph(g)
                self.cfg = cfg.clamped(g.num_vertices)
                self._graph = g
                self.n, self.m = g.num_vertices, g.num_edges
                self._edges = np.asarray(g.edges)
                self.limit = alpha_limit(self.cfg.alpha, self.m,
                                         self.cfg.num_partitions)
                self.state: NEState | SpmdState = ne_init_state(g, self.cfg)
            elif self.multihost:
                self._init_multihost(source, cfg, num_devices, snapshot_dir,
                                     exchange_dir)
            else:
                self._graph_fp = graph_fingerprint(source)
                d = num_devices or len(jax.devices())
                self.num_devices = max(1, min(d, len(jax.devices())))
                with obs.span("ingest_shards", cat="runtime"):
                    self.n, self.m, self._edges, shards, masks, self._dev = \
                        self._ingest(source, self.num_devices, num_hosts,
                                     ingest_processes)
                self.cfg = cfg.clamped(self.n)
                self.limit = alpha_limit(self.cfg.alpha, self.m,
                                         self.cfg.num_partitions)
                with obs.span("ingest_place", cat="runtime"):
                    self.mesh = make_edge_mesh(self.num_devices, axis=AXIS)
                    self._u_sh = shard_over(self.mesh, shards[:, :, 0])
                    self._v_sh = shard_over(self.mesh, shards[:, :, 1])
                    self._mask_sh = shard_over(self.mesh, masks)
                    self.state = spmd_init_state(shards, masks, self.n,
                                                 self.cfg, self.mesh)

        # per-round SyncVertexAllocations traffic (per device) — a pure
        # function of the config, recorded as a cumulative trace counter
        self._sync_bytes = (0 if mode in ("single", "hybrid") else
                            round_sync_payload_bytes(self.cfg, self.n,
                                                     self.num_devices))
        self._sync_total = 0
        # and the slots one trip of its fold scatters (sync_chunk)
        self._sync_chunk = (sync_chunk(int(self._mask_sh.shape[1]))
                            if self._sync_bytes else 0)
        if live.live_enabled():
            live.publish(phase="ingest", round=0, edges_remaining=self.m)
        self.snapshot = (RunSnapshot(snapshot_dir, self.cfg, self._graph_fp,
                                     keep=keep)
                        if snapshot_dir is not None else None)

    def _init_multihost(self, source, cfg: NEConfig,
                        num_devices: int | None, snapshot_dir, exchange_dir):
        """True multi-controller construction (``jax.process_count() > 1``).

        Each process streams only its own host block range into the
        cluster exchange, assembles only the shards of the devices it
        owns, and the round state is built as global ``jax.Array``\\ s
        over the all-process mesh.  The full edge list / device map are
        *not* materialized here — the finalize epilogue loads them lazily
        from the exchange.
        """
        from repro.runtime import multihost as mh

        if not isinstance(source, EdgeFile):
            raise TypeError(
                "multi-controller runs partition a canonical EdgeFile — "
                "every process must ingest its own block range, got "
                f"{type(source).__name__}")
        require_canonical(source)
        self._graph_fp = graph_fingerprint(source)
        if num_devices not in (None, len(jax.devices())):
            raise ValueError(
                f"num_devices={num_devices} under jax.distributed — the "
                f"mesh always spans all {len(jax.devices())} global "
                f"devices (one shard per device)")
        self.num_devices = len(jax.devices())
        if exchange_dir is None and snapshot_dir is not None:
            exchange_dir = os.path.join(os.fspath(snapshot_dir), "exchange")
        if exchange_dir is None:
            raise ValueError("multi-controller ingestion needs an "
                             "exchange_dir (or a snapshot_dir to derive "
                             "it from)")
        self._exchange_dir = os.fspath(exchange_dir)
        self.n, self.m = int(source.num_vertices), int(source.num_edges)
        self.cfg = cfg.clamped(self.n)
        self.limit = alpha_limit(self.cfg.alpha, self.m,
                                 self.cfg.num_partitions)
        self.mesh = make_edge_mesh(self.num_devices, axis=AXIS)
        self._owned = mh.owned_indices(self.mesh)
        cluster.exchange_write_range(self._exchange_dir, source.path,
                                     self._host, self._nprocs,
                                     self.num_devices)
        compat.barrier("ingest-exchange")
        shards, masks, cap, degree = cluster.exchange_assemble(
            self._exchange_dir, self._nprocs, self.num_devices, self._owned)
        self._u_sh = mh.global_shard_array(
            self.mesh, {d: shards[d][:, 0] for d in self._owned},
            (cap,), np.int32)
        self._v_sh = mh.global_shard_array(
            self.mesh, {d: shards[d][:, 1] for d in self._owned},
            (cap,), np.int32)
        self._mask_sh = mh.global_shard_array(
            self.mesh, {d: masks[d] for d in self._owned}, (cap,), bool)
        self.state = mh.spmd_init_state_global(
            self.mesh, cap, self.n, self.cfg, degree, self.m, self._owned)
        # loaded lazily by finalize() from the exchange — the round loop
        # never holds O(M) host state in a multi-controller run
        self._edges = None
        self._dev = None

    @staticmethod
    def _ingest(source, num_devices: int, num_hosts: int | None,
                processes: bool):
        """Edge shards + metadata, via the multi-host plan for store
        handles (cluster block ranges) or in-memory for a Graph."""
        if isinstance(source, Graph):
            edges = np.asarray(source.edges)
            shards, masks, _, dev = shard_edges(edges, num_devices)
            return (source.num_vertices, source.num_edges, edges, shards,
                    masks, dev)
        if not isinstance(source, EdgeFile):
            raise TypeError("PartitionDriver takes a Graph or a canonical "
                            f"EdgeFile, got {type(source).__name__}")
        require_canonical(source)
        shards, masks, _, dev, edges = cluster.ingest_edgefile(
            source, num_devices, num_hosts=num_hosts, processes=processes,
            with_edges=True)
        return (int(source.num_vertices), int(source.num_edges), edges,
                shards, masks, dev)

    # -- state machine ------------------------------------------------------

    @property
    def rounds(self) -> int:
        return int(self.state.rounds)

    @property
    def done(self) -> bool:
        # cached per state: run() + step() both consult it every round, and
        # the single-controller check is a full edge_part host transfer
        if self._done is None:
            if self.m == 0:
                self._done = True
            elif self.mode in ("single", "hybrid"):
                # HybridConfig carries max_rounds, so ne_done reads either
                with obs.span("done_read", cat="runtime"):
                    self._done = ne_done(self.state, self.cfg)
            else:
                # the host mirror of the whole-run while_loop condition
                with obs.span("done_read", cat="runtime"):
                    self._done = (self._remaining() <= 0
                                  or self.rounds >= self.cfg.max_rounds)
        return self._done

    def _remaining(self) -> int:
        """The SPMD state's unallocated edges, read from the device once
        per state.  The drop since the previous read is what the rounds
        between them folded, both syncs of each (``sync_new_edges``)."""
        if self._rem is None:
            self._rem = int(self.state.remaining)
            if self._rem_last is not None:
                self._new_edges = self._rem_last - self._rem
            self._rem_last = self._rem
        return self._rem

    def _state_changed(self, restored: bool = False):
        """Drop what was read from or built on the previous state; after
        a restore the next read of ``remaining`` starts the count anew."""
        self._result = None
        self._final_slices = None
        self._done = None
        self._rem = None
        if restored:
            self._rem_last = self._new_edges = None

    def step(self) -> int:
        """Advance one paper round; returns the completed round count.

        Stepping past :attr:`done` is a no-op (the driver never runs the
        round function on a finished state, matching the while_loop cond).
        """
        if self.done:
            return self.rounds
        # the round span covers the snapshot save too (nested "snapshot"
        # span): per-round cost as a long run pays it, matching the old
        # hand-timed round_secs the multihost_snap bench row diffs.  An
        # SPMD round's span carries what its sync sends, so a profiler
        # trace holds the count beside the exchange's device time, and
        # the sync's chunk with the new edges the round before folded
        args = {}
        if self._sync_bytes:
            args = {"sync_payload_bytes": self._sync_bytes,
                    "sync_chunk": self._sync_chunk}
            if self._new_edges is not None:
                args["sync_new_edges"] = self._new_edges
        with obs.span("round", cat="runtime", **args) as sp:
            # the call returns once the round is enqueued; the wait is the
            # device's time, the read the scalar the step returns
            with obs.span("round_dispatch", cat="runtime"):
                if self.mode in ("single", "hybrid"):
                    cfg = self.cfg if self.mode == "single" else self._necfg
                    state = ne_round_step(self._graph, cfg, self.limit,
                                          self.state)
                else:
                    with jax.set_mesh(self.mesh):
                        state = spmd_round_step(
                            self.cfg, self.limit, self.n, self.mesh,
                            self._u_sh, self._v_sh, self._mask_sh,
                            self.state)
            with obs.span("round_wait", cat="runtime"):
                self.state = jax.block_until_ready(state)
            with obs.span("round_read", cat="runtime"):
                rounds = self.rounds
            self._state_changed()
            tr = obs.get_tracer()
            if tr is not None:
                sp.set(round=rounds)
                if self.mode == "spmd":
                    tr.counter("edges_remaining", self._remaining())
                    tr.counter("sync_new_edges", self._new_edges)
                    tr.add("sync_payload_bytes", self._sync_bytes)
            self._sync_total += self._sync_bytes
            if live.live_enabled():
                # pure read of the replicated state (no RNG, no mutation),
                # so monitored runs stay bit-identical to unmonitored
                q = round_quality(self.cfg, self.state, self.n)
                rem = (self._remaining() if self.mode == "spmd"
                       else q["degree_sum"] // 2)
                live.publish(phase="round", round=rounds,
                             edges_remaining=rem,
                             sync_payload_bytes=self._sync_total,
                             rf=q["rf"], eb=q["eb"], vb=q["vb"],
                             boundary=q["boundary"])
            if (self.snapshot is not None and self.snapshot_every
                    and rounds % self.snapshot_every == 0):
                self.save_snapshot()
        return rounds

    def run(self) -> PartitionResult:
        """Step to the fixed point (snapshotting as configured), finalize."""
        while not self.done:
            self.step()
        return self.finalize()

    def finalize(self) -> PartitionResult:
        """Cleanup epilogue; cached until the state advances.

        Single-controller: stitch + whole-array cleanup
        (``finalize_result``).  Multi-controller: the sharded epilogue —
        slice-local cleanup, collective metrics combine, lazy
        ``edge_part`` (see :meth:`_finalize_multihost`).
        """
        if self._result is not None:
            return self._result
        p_num = self.cfg.num_partitions
        if self.m == 0:
            self._result = PartitionResult(
                np.zeros((0,), np.int32), np.zeros((self.n, p_num), bool),
                np.zeros((p_num,), np.int32), 0, 0)
            self._publish_live_done()
            return self._result
        with obs.span("finalize", cat="runtime", mode=self.mode):
            if self.mode == "hybrid":
                from repro.core.hybrid import hybrid_finalize

                self._result = hybrid_finalize(self.state, self._split,
                                               self.cfg)
                self._publish_live_done()
                return self._result
            if self.multihost:
                self._result = self._finalize_multihost()
                self._publish_live_done()
                return self._result
            st = self.state
            with obs.span("device_get", cat="runtime"):
                edge_part, vparts, counts, rounds = jax.device_get(
                    (st.edge_part, st.vparts, st.edges_per_part, st.rounds))
            if self.mode == "spmd":
                with obs.span("stitch_edge_part", cat="runtime"):
                    edge_part = stitch_edge_part(edge_part, self._dev, self.m)
                if self.cfg.use_pallas:
                    # SPMD round state keeps replica sets bit-packed; the
                    # result surface is always (N, P) bool
                    vparts = ne_ops.unpack_bits_np(vparts, p_num)
            with obs.span("finalize_result", cat="runtime"):
                self._result = finalize_result(edge_part, vparts, counts,
                                               self._edges, self.cfg,
                                               int(rounds))
            self._publish_live_done()
            return self._result

    def _publish_live_done(self):
        """Terminal bus snapshot: the finalized (post-cleanup) quality,
        flagged ``done`` so the monitor can distinguish a finished run
        from a stalled one."""
        if not live.live_enabled():
            return
        st = self._result.stats if self._result is not None else None
        live.publish(
            phase="done", round=self.rounds, edges_remaining=0,
            sync_payload_bytes=self._sync_total,
            rf=st.replication_factor if st is not None else None,
            eb=st.edge_balance if st is not None else None,
            vb=st.vertex_balance if st is not None else None,
            done=True)

    def _owned_host_slices(self, arr) -> dict:
        """Host-side copies of the owned device slices of a (D, C) global
        array — O(owned × C), never O(M)."""
        slices = {}
        for sh in arr.addressable_shards:
            i = sh.index[0].start or 0
            slices[int(i)] = np.array(sh.data)[0]
        return slices

    def _finalize_multihost(self) -> PartitionResult:
        """The sharded finalize epilogue (see repro.runtime.finalize).

        Every per-edge structure touched here is an owned-slice dict; the
        only cross-host state is the sorted leftover-eid spills plus two
        ``compat`` collectives (scalar leftover sum, O(N·P) replica OR).
        The returned result's ``edge_part`` is lazy — forcing it is the
        one deliberate O(M) gather, for small graphs and tests.
        """
        from repro.runtime import finalize as fz

        p_num = self.cfg.num_partitions
        ep = self._owned_host_slices(self.state.edge_part)
        us = self._owned_host_slices(self._u_sh)
        vs = self._owned_host_slices(self._v_sh)
        eids = cluster.shard_eids(self._exchange_dir, self._nprocs,
                                  self._owned)
        counts = np.array(self.state.edges_per_part)       # replicated
        vparts = np.array(self.state.vparts)               # replicated
        if self.cfg.use_pallas:  # round state is bit-packed words
            vparts = ne_ops.unpack_bits_np(vparts, p_num)
        rounds = self.rounds

        fin_dir = os.path.join(self._exchange_dir, "finalize")
        my_left = fz.stage_leftovers(fin_dir, self._host, ep, eids)
        total = compat.all_processes_sum(my_left.size)
        compat.barrier("finalize-leftovers")
        take, _ = fz.apply_leftovers(
            fin_dir, self._host, self._nprocs, my_left, ep, us, vs, eids,
            counts, self.limit, p_num, vparts, leftover_total=total)
        # metrics-combine: per-host replica deltas OR-merge (O(N·P)),
        # counts update is the shared plan itself — no per-edge traffic
        vparts = compat.all_processes_any(vparts)
        counts = (counts.astype(np.int64) + take).astype(np.int32)
        stats = stats_from_counts(vparts.sum(axis=0), counts, self.n)

        self._final_slices = (ep, us, vs, eids)
        # capture only what materialization needs — closing over the
        # whole SpmdState would pin every device-side round array for
        # the lifetime of the result
        mesh, ep_global = self.mesh, self.state.edge_part
        exchange_dir, nprocs, m = self._exchange_dir, self._nprocs, self.m

        def materialize() -> np.ndarray:
            if os.environ.get("REPRO_FORBID_EDGE_PART_MATERIALIZE"):
                raise RuntimeError(
                    "REPRO_FORBID_EDGE_PART_MATERIALIZE is set: the "
                    "multi-process epilogue must never materialize the "
                    "O(M) global edge assignment")
            from repro.runtime import multihost as mh

            ep_sh = mh.gather_to_host(mesh, ep_global)
            _, dev = cluster.exchange_read_global(exchange_dir, nprocs)
            full = stitch_edge_part(ep_sh, dev, m)
            left_eids, left_tgt = fz.leftover_assignments(fin_dir, nprocs,
                                                          take)
            full[left_eids] = left_tgt
            return full

        return PartitionResult(materialize, vparts, counts, rounds,
                               int(total), stats)

    # -- snapshots ----------------------------------------------------------

    def save_snapshot(self):
        """Persist the current round state (crash-safe, fingerprinted).

        Multi-controller runs go through the cooperative multi-writer
        protocol: this process writes only the ``edge_part`` slices of the
        devices it owns, process 0 stages the replicated fields and
        publishes the round atomically once every host's slices are
        durably staged (see ``RunSnapshot.save_state_multihost``).
        """
        if self.snapshot is None:
            raise RuntimeError("driver was built without a snapshot_dir")
        with obs.span("snapshot", cat="runtime", round=self.rounds):
            if self.multihost:
                slices = {}
                for sh in self.state.edge_part.addressable_shards:
                    i = sh.index[0].start or 0
                    slices[int(i)] = np.asarray(sh.data)[0]
                fields = {k: np.asarray(v)
                          for k, v in self.state._asdict().items()
                          if k != "edge_part"}
                return self.snapshot.save_state_multihost(
                    self.rounds, fields, self.mode, self._host,
                    {"edge_part": slices}, {"edge_part": self.num_devices},
                    compat.barrier, fault_hook=self.snapshot_fault_hook)
            fields = {k: np.asarray(v)
                      for k, v in self.state._asdict().items()}
            return self.snapshot.save_state(self.rounds, fields, self.mode)

    def restore_snapshot(self, round_k: int | None = None) -> int:
        """Load round state from the snapshot store (latest by default).

        Multi-controller resume is barrier'd: each process loads only its
        own ``edge_part`` slices of the newest round it can fully read,
        the processes agree on the minimum such round (so one host's torn
        shard rolls everyone back together), rebuild the global state, and
        synchronize before the first step.
        """
        if self.snapshot is None:
            raise RuntimeError("driver was built without a snapshot_dir")
        if self.multihost:
            with obs.span("restore", cat="runtime"):
                return self._restore_multihost(round_k)
        with obs.span("restore", cat="runtime"):
            return self._restore_single(round_k)

    def _restore_single(self, round_k: int | None) -> int:
        fields, rnd, mode = self.snapshot.restore_state(round_k)
        if mode != self.mode:
            raise SnapshotMismatch(f"snapshot was taken in mode {mode!r}, "
                                   f"driver is {self.mode!r}")
        cls = SpmdState if self.mode == "spmd" else NEState
        want = cls._fields
        missing = set(want) - set(fields)
        if missing:
            raise SnapshotMismatch(f"snapshot is missing fields {missing}")
        if self.mode == "spmd":
            have = tuple(fields["edge_part"].shape)
            expect = tuple(self._mask_sh.shape)
            if have != expect:
                # elastic resume: the snapshot was taken on a different
                # device count — reshard the slices onto the current
                # layout instead of refusing (single-controller, so the
                # in-memory stitch + re-split is the honest path)
                fields["edge_part"] = self._reshard_in_memory(
                    np.asarray(fields["edge_part"]))
        state = cls(**{k: np.asarray(fields[k]) for k in want})
        self.state = (place_state(self.mesh, state) if self.mode == "spmd"
                      else jax.tree.map(jnp.asarray, state))
        self._state_changed(restored=True)
        return rnd

    def _reshard_in_memory(self, old: np.ndarray) -> np.ndarray:
        """Single-controller elastic reshard: old (D_old, C_old) slices →
        the current (D, C) layout, preserving every per-edge value.  The
        shard layout is a pure function of the 2D hash, so the old
        per-edge device map re-derives deterministically."""
        from repro.io.csr import grid_assign_host

        d_old = old.shape[0]
        dev_old = grid_assign_host(self._edges, d_old)
        full = stitch_edge_part(old, dev_old, self.m)
        new = np.full(tuple(self._mask_sh.shape), -1, np.int32)
        for d in range(new.shape[0]):
            sel = np.flatnonzero(self._dev == d)
            new[d, : sel.size] = full[sel]
        return new

    def _restore_multihost(self, round_k: int | None) -> int:
        from repro.runtime import multihost as mh

        load = dict(num_devices=self.num_devices, host=self._host,
                    num_hosts=self._nprocs)
        fields, rnd, mode, counts = \
            self.snapshot.restore_state_multihost(self._owned, round_k,
                                                  **load)
        if round_k is None:
            agreed = compat.all_processes_min(rnd)
            if agreed != rnd:
                fields, rnd, mode, counts = \
                    self.snapshot.restore_state_multihost(
                        self._owned, round_k=agreed, **load)
        if mode != self.mode:
            raise SnapshotMismatch(f"snapshot was taken in mode {mode!r}, "
                                   f"driver is {self.mode!r}")
        missing = set(SpmdState._fields) - set(fields)
        if missing:
            raise SnapshotMismatch(f"snapshot is missing fields {missing}")
        cap = int(self._mask_sh.shape[1])
        d_old = counts.get("edge_part")
        if d_old != self.num_devices:
            # elastic resume onto a different device count: the loaded
            # slices follow the balanced *old* layout — reshard them
            # through the store-backed exchange (O(m/H) per process)
            slices = self._reshard_multihost(fields["edge_part"], d_old,
                                             cap, rnd)
        else:
            slices = fields["edge_part"]
            for i, arr in slices.items():
                if tuple(arr.shape) != (cap,):
                    raise SnapshotMismatch(
                        f"snapshot edge_part shard {i} has shape "
                        f"{arr.shape} != current capacity ({cap},)")
        edge_part = mh.global_shard_array(self.mesh, slices, (cap,),
                                          np.int32)
        rep = {k: mh.replicate(self.mesh, fields[k])
               for k in SpmdState._fields if k != "edge_part"}
        self.state = SpmdState(edge_part=edge_part, **rep)
        self._state_changed(restored=True)
        compat.barrier(f"resume-{rnd}")
        return rnd

    def _reshard_multihost(self, old_slices: dict, d_old: int, cap: int,
                           rnd: int) -> dict:
        """Elastic multihost reshard: stage my old slices' (eid, value)
        pairs per new device, barrier, assemble my owned new slices —
        see ``repro.runtime.cluster.reshard_write``."""
        spill = os.path.join(self._exchange_dir,
                             f"reshard_{rnd:010d}_{d_old}to"
                             f"{self.num_devices}")
        cluster.reshard_write(spill, self._exchange_dir, self._nprocs,
                              old_slices, d_old, self.num_devices,
                              self._host)
        compat.barrier(f"reshard-{rnd}")
        return cluster.reshard_assemble(spill, self._nprocs, self._owned,
                                        cap)

    @classmethod
    def resume(cls, source, cfg: NEConfig,
               snapshot_dir: str | os.PathLike, round_k: int | None = None,
               **kwargs) -> "PartitionDriver":
        """Rebuild a driver from ``snapshot_dir`` and continue from the
        latest (or ``round_k``-th) snapshot.  The edge shards are re-derived
        from ``source``; the snapshot's fingerprints guarantee that is the
        same derivation the interrupted run made."""
        drv = cls(source, cfg, snapshot_dir=snapshot_dir, **kwargs)
        drv.restore_snapshot(round_k)
        return drv

    # -- durable output -----------------------------------------------------

    def save_artifact(self, dirpath: str | os.PathLike) -> PartitionArtifact:
        """Finalize and persist the run's output as a partition artifact.

        Multi-controller runs go through the cooperative multi-writer
        protocol: every process calls this, each writes only its owned
        slices' shards, and the published bytes are identical to a
        single-writer save of the same result (no host ever holds the
        global assignment).
        """
        res = self.finalize()
        if self.multihost:
            return self._save_artifact_multihost(dirpath, res)
        if self._edges is None:
            # hybrid mode never holds the source edge list for the round
            # loop; the artifact save is the one consumer that needs it
            self._edges = (self.source.read_all()
                           if isinstance(self.source, EdgeFile)
                           else np.asarray(as_graph(self.source).edges))
        with obs.span("save_artifact", cat="runtime"):
            return save_artifact(
                dirpath, res, self._edges, self.n,
                config_fingerprint=config_fingerprint(self.cfg),
                graph_fingerprint=self._graph_fp)

    def _save_artifact_multihost(self, dirpath, res) -> PartitionArtifact:
        from repro.runtime import artifact as art
        from repro.runtime import finalize as fz

        p_num = self.cfg.num_partitions
        if self._final_slices is None:
            # m == 0: finalize took the eager empty-result path, nothing
            # is sharded — writer-0 runs the single-writer save
            if self._host == 0:
                save_artifact(
                    dirpath, res, np.zeros((0, 2), np.int32), self.n,
                    config_fingerprint=config_fingerprint(self.cfg),
                    graph_fingerprint=self._graph_fp)
            compat.barrier("artifact-empty")
            return PartitionArtifact(dirpath)
        ep, us, vs, eids = self._final_slices
        if self._host == 0:
            art.begin_shared_artifact(dirpath)
        compat.barrier("artifact-begin")
        contribs = fz.partition_contribs(ep, us, vs, eids, p_num)
        art.write_artifact_contrib(dirpath, self._host, contribs)
        compat.barrier("artifact-contrib")
        owned_parts = list(range(self._host, p_num, self._nprocs))
        art.encode_shared_parts(dirpath, self._host, owned_parts,
                                self._nprocs)
        compat.barrier("artifact-encode")
        if self._host == 0:
            art.publish_shared_artifact(
                dirpath, num_vertices=self.n, num_edges=self.m,
                num_partitions=p_num, num_hosts=self._nprocs,
                vparts=res.vparts, edges_per_part=res.edges_per_part,
                rounds=res.rounds, leftover=res.leftover,
                config_fingerprint=config_fingerprint(self.cfg),
                graph_fingerprint=self._graph_fp)
        compat.barrier("artifact-publish")
        return PartitionArtifact(dirpath)


__all__ = ["PartitionDriver"]
