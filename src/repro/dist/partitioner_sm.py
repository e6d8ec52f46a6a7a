"""SPMD Distributed NE — the paper's §4 algorithm under ``shard_map``.

The input graph is 2D-hash edge-partitioned across devices
(``core.graph.shard_edges``): device ``d`` holds an equal-length padded
shard of the undirected edge list and allocates *only its own edges*.
One while_loop step == one paper round, per device:

  1. **selection** — every device computes the same per-vertex claim keys
     from the replicated global state (``core.partitioner.vertex_claims``).
     The paper's per-machine selection collapses to this replicated compute
     because selection reads only V(E_p), D_rest and |E_p|, all of which
     are re-synchronized at the end of every round;
  2. **one-hop allocation** over local edges: edge (u, v) joins the best
     claiming partition ``min(claim[u], claim[v])``
     (``core.partitioner.one_hop`` over the local shard);
  3. **SyncVertexAllocations** — the paper's §4 merge, realized as an OR
     all-reduce of the replica-set deltas plus ``psum`` of the |E_p| and
     D_rest deltas, each device's deltas scattered from its batch's new
     edges alone (a sort brings them to the front, then fixed chunks);
  4. **two-hop "free edge" allocation** (Condition (5)) over local edges,
     with the per-round α-capacity quota split deterministically across
     devices by an exclusive prefix over the device axis (an ``all_gather``
     of per-device candidate histograms).

Steps 2–4 touch only the local shard, so per-round work scales 1/D; the
sync in step 3 is the round barrier the paper describes.  Each step runs
under a ``jax.named_scope`` — ``ne_select``, ``ne_one_hop``, ``ne_sync``
(both ``_apply_alloc`` calls) and ``ne_two_hop`` — which names every op's
phase in a profiler trace; each collective call alone sits in a nested
``ne_exchange`` scope, so the cross-device exchange reads apart from the
local work of its phase.

This is the one round body: :func:`partition` is :func:`partition_spmd` on
a one-device mesh, the hybrid's expansion phase runs it over the low
subgraph (``repro.core.hybrid``), and ``repro.runtime.PartitionDriver``
steps it one round at a time (:func:`spmd_round_step`).  On more devices
only the two-hop quota *ordering* in step 4 depends on the device count,
so the quality (replication factor) stays close to the one-device result
— asserted by tests/test_spmd.py.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.epilogue import stitch_slices
from repro.core.graph import (Graph, as_graph, exclusive_rank,
                              shard_edges, target_histogram)
from repro.core.partitioner import (I32_INF, NEConfig, PartitionResult,
                                    alpha_limit, finalize_result,
                                    one_hop, priority_enc, vertex_claims)
from repro.dist import compat
from repro.io.edgefile import EdgeFile
from repro.kernels.ne_round import ops as ne_ops
from repro.io.stream import require_canonical, shard_edges_stream

AXIS = "shard"
# the scope of the round's collective calls, and of nothing else
EXCHANGE = "ne_exchange"
Array = jax.Array


class SpmdState(NamedTuple):
    edge_part: Array        # (C,)   int32 per-device shard, -1 = unallocated
    vparts: Array           # (N, P) bool replica sets — replicated; with
    #                         cfg.use_pallas, bit-packed (N, ceil(P/32))
    #                         uint32 words (repro.kernels.ne_round)
    degree_rest: Array      # (N,)   int32 — replicated
    edges_per_part: Array   # (P,)   int32 — replicated
    key: Array              # PRNG key — replicated
    rounds: Array           # ()     int32
    remaining: Array        # ()     int32 unallocated edges, global


# the round state's layout: ``edge_part`` sharded over the device axis,
# every other field replicated
STATE_SPECS = SpmdState(P(AXIS, None), *(P(),) * 6)


def sync_chunk(c: int) -> int:
    """Slots one trip of the sync's fold scatters, from the shard length
    C alone: the power of two nearest C/64, within [2**12, 2**16] and no
    more than C.  A batch of every slot then takes at most ~64 trips, and
    the last trip's unused tail is about 1/128 of C on average."""
    k = 1 << max(0, round(math.log2(max(c, 1) / 64)))
    return max(1, min(c, max(1 << 12, min(k, 1 << 16))))


def _fold_new(new, n_new, part, u_loc, v_loc, n, p_num):
    """This device's replica-set delta (N, P) and D_rest decrement (N,)
    for one batch, scattered from its ``n_new`` new slots alone.

    One sort brings the new slots' indices to the front (every other slot
    keys as C, and sorts behind them); then ceil(n_new / K) trips of
    :func:`sync_chunk`'s K each gather K indices' endpoints and partition
    and scatter them, the tail of the last trip dropped.  Sets are ORs and
    decrements integer adds, so the order the slots fold in cannot change
    the result."""
    c = new.shape[0]
    k = sync_chunk(c)
    pad = -c % k
    keys = jnp.where(jnp.pad(new, (0, pad)),
                     jnp.arange(c + pad, dtype=jnp.int32), c)
    order = jax.lax.sort(keys, is_stable=False)     # the keys are distinct

    def trip(t, acc):
        vnew, dec = acc
        idx = jax.lax.dynamic_slice(order, (t * k,), (k,))
        u = u_loc.at[idx].get(mode="fill", fill_value=n)
        v = v_loc.at[idx].get(mode="fill", fill_value=n)
        p = part.at[idx].get(mode="fill", fill_value=0)
        vnew = vnew.at[u, p].set(True, mode="drop")
        vnew = vnew.at[v, p].set(True, mode="drop")
        dec = dec.at[u].add(1, mode="drop").at[v].add(1, mode="drop")
        return vnew, dec

    return jax.lax.fori_loop(
        0, (n_new + k - 1) // k, trip,
        (jnp.zeros((n, p_num), bool), jnp.zeros((n,), jnp.int32)))


@jax.named_scope("ne_sync")
def _apply_alloc(new, part, u_loc, v_loc, n, p_num, vparts, degree_rest,
                 edges_per_part, num_dev, local_counts=None):
    """Fold one local allocation batch into the replicated state.

    ``psum`` of the per-device deltas + OR of the replica-set delta ==
    the paper's SyncVertexAllocations.  The deltas are scattered from the
    batch's new slots alone (:func:`_fold_new`).  When ``vparts`` arrives
    bit-packed (uint32 words — cfg.use_pallas), the replica-set delta is
    packed *before* the collective, so the all-reduce moves
    (N, ceil(P/32))·4 bytes instead of the bool path's (N, P)·4-byte int32
    psum — exact OR either way, hence bit-identical replica sets after
    unpacking.  The (P,) count delta, unless the caller has it as
    ``local_counts``, is a ``target_histogram`` of the batch (C·P
    compare-adds, no scatter).
    """
    counts = local_counts
    if counts is None:
        counts = target_histogram(jnp.where(new, part, -1), p_num)
    vnew, dec = _fold_new(new, counts.sum(), part, u_loc, v_loc, n, p_num)
    with jax.named_scope(EXCHANGE):
        counts = jax.lax.psum(counts, AXIS)
    if vparts.dtype == jnp.uint32:
        words = ne_ops.pack_bits(vnew)
        with jax.named_scope(EXCHANGE):
            delta = compat.or_all_reduce(words, AXIS, num_dev)
        vparts = ne_ops.or_words(vparts, delta)
    else:
        with jax.named_scope(EXCHANGE):
            hits = jax.lax.psum(vnew.astype(jnp.int32), AXIS)
        vparts = vparts | (hits > 0)
    with jax.named_scope(EXCHANGE):
        dec = jax.lax.psum(dec, AXIS)
    degree_rest = degree_rest - dec
    return vparts, degree_rest, edges_per_part + counts, counts.sum()


def _spmd_round(cfg: NEConfig, limit: int, n: int, num_dev: int,
                u_loc: Array, v_loc: Array, mask_loc: Array,
                state: SpmdState) -> SpmdState:
    p_num = cfg.num_partitions
    packed = cfg.use_pallas

    # --- 1. replicated selection + claims ----------------------------------
    # the packed replica map unpacks once per round for selection; every
    # other consumer below reads the packed words directly
    with jax.named_scope("ne_select"):
        key, sub = jax.random.split(state.key)
        vparts_rep = (ne_ops.unpack_bits(state.vparts, p_num) if packed
                      else state.vparts)
    vclaim = vertex_claims(cfg, limit, vparts_rep, state.degree_rest,
                           state.edges_per_part, sub)

    # --- 2. one-hop allocation on the local shard --------------------------
    part1, counts1 = one_hop(vclaim, u_loc, v_loc, state.edge_part, p_num,
                             mask=mask_loc)
    with jax.named_scope("ne_one_hop"):
        new1 = part1 >= 0
        edge_part = jnp.where(new1, part1, state.edge_part)

    # --- 3. SyncVertexAllocations ------------------------------------------
    vparts, degree_rest, edges_per_part, new_total = _apply_alloc(
        new1, part1, u_loc, v_loc, n, p_num, state.vparts,
        state.degree_rest, state.edges_per_part, num_dev,
        local_counts=counts1)

    # --- 4. two-hop free edges, Condition (5) ------------------------------
    if cfg.two_hop:
        with jax.named_scope("ne_two_hop"):
            enc_vec = priority_enc(edges_per_part,
                                   jnp.arange(p_num, dtype=jnp.int32), p_num)
            enc_vec = jnp.where(edges_per_part <= limit, enc_vec, I32_INF)
            quota = jnp.maximum(limit + 1 - edges_per_part, 0)
            unal = mask_loc & (edge_part < 0)
            # candidates + local ranks, scanned in edge_chunk-sized chunks so
            # peak memory is edge_chunk × P
            c_len = u_loc.shape[0]
            ce = min(cfg.edge_chunk, c_len)
            n_ec = (c_len + ce - 1) // ce
            pad = n_ec * ce - c_len
            u_p = jnp.pad(u_loc, (0, pad))
            v_p = jnp.pad(v_loc, (0, pad))
            un_p = jnp.pad(unal, (0, pad))                  # pads → False

            def cand_chunk(counts, args):
                uu, vv, un = args
                if packed:
                    # gather packed words (32× less traffic), unpack per chunk
                    inter = ne_ops.unpack_bits(vparts[uu] & vparts[vv], p_num)
                else:
                    inter = vparts[uu] & vparts[vv]                   # (ce, P)
                k2 = jnp.where(inter & un[:, None], enc_vec[None, :], I32_INF)
                best = k2.min(axis=1)
                cand_c = jnp.where(best < I32_INF,
                                   (best % p_num).astype(jnp.int32), -1)
                rank_c = exclusive_rank(cand_c, p_num) \
                    + counts[jnp.maximum(cand_c, 0)]
                counts = counts + target_histogram(cand_c, p_num)
                return counts, (cand_c, rank_c)

            hist, (cand, myrank) = jax.lax.scan(
                cand_chunk, jnp.zeros((p_num,), jnp.int32),
                (u_p.reshape(n_ec, ce), v_p.reshape(n_ec, ce),
                 un_p.reshape(n_ec, ce)))
            cand = cand.reshape(-1)[:c_len]
            myrank = myrank.reshape(-1)[:c_len]
            cand0 = jnp.maximum(cand, 0)
            # deterministic cross-device quota split: device d's candidates for
            # partition p rank after all candidates on devices < d.  The
            # (D, P) gather of the histograms is the psum of each device's
            # own row: the compiler makes an all-reduce of an all_gather
            # here anyway, and drops the op_name on the way
            r = jax.lax.axis_index(AXIS)
            rows = jnp.arange(num_dev)[:, None]
            own = jnp.where(rows == r, hist[None, :], 0)
            with jax.named_scope(EXCHANGE):
                hists = jax.lax.psum(own, AXIS)                       # (D, P)
            before = jnp.where(rows < r, hists, 0).sum(axis=0)        # (P,)
            keep = (cand >= 0) & (before[cand0] + myrank < quota[cand0])
            part2 = jnp.where(keep, cand, -1)
            edge_part = jnp.where(keep, part2, edge_part)
            vparts, degree_rest, edges_per_part, new2 = _apply_alloc(
                keep, part2, u_loc, v_loc, n, p_num, vparts, degree_rest,
                edges_per_part, num_dev)
            new_total = new_total + new2

    return SpmdState(edge_part, vparts, degree_rest, edges_per_part, key,
                     state.rounds + 1, state.remaining - new_total)


# ---------------------------------------------------------------------------
# round-stepping surface (repro.runtime.driver)
# ---------------------------------------------------------------------------

def empty_vparts(n: int, cfg: NEConfig) -> np.ndarray:
    """All-empty replica sets, on the host, in the representation the
    round uses: bit-packed uint32 words under cfg.use_pallas, (N, P) bool
    otherwise."""
    if cfg.use_pallas:
        w = ne_ops.replica_words(cfg.num_partitions)
        return np.zeros((n, w), np.uint32)
    return np.zeros((n, cfg.num_partitions), bool)


def make_edge_mesh(num_devices: int | None = None) -> jax.sharding.Mesh:
    """1-D edge-shard mesh for the round, single- or multi-process.

    Uses the *global* device list, which ``jax.devices()`` orders by
    process index then local device id — so under ``jax.distributed`` every
    process builds the identical mesh and process ``h`` owns the contiguous
    device range ``[h·L, (h+1)·L)``.  That contiguity is what lets the
    runtime's host block ranges, per-host shard files and snapshot shard
    indices all share one numbering.
    """
    devs = jax.devices()
    d = num_devices or len(devs)
    if d > len(devs):
        raise ValueError(f"requested {d} devices, only {len(devs)} exist")
    return compat.make_mesh((d,), (AXIS,), devices=devs[:d])


def shard_over(mesh, x) -> Array:
    """Place a (D, ...) host array on ``mesh``, row ``d`` on device ``d``."""
    return jax.device_put(x, NamedSharding(mesh, P(AXIS, None)))


def place_state(mesh, state: SpmdState) -> SpmdState:
    """Place a round state on ``mesh`` in the layout of :data:`STATE_SPECS`:
    the one :func:`spmd_round_step` takes and returns, so no round
    reshards."""
    return jax.device_put(state, SpmdState(
        *(NamedSharding(mesh, spec) for spec in STATE_SPECS)))


def spmd_init_state(shards: np.ndarray, masks: np.ndarray, n: int,
                    cfg: NEConfig, mesh) -> SpmdState:
    """The initial round state, built on the host (global D_rest in one
    bincount pass) and placed on ``mesh``: the one state every run starts
    from.  ``edge_part`` keeps its (D, C) shard layout, sharded over the
    device axis.
    """
    p_num = cfg.num_partitions
    flat = shards.reshape(-1, 2)[masks.reshape(-1)]
    degree = (np.bincount(flat[:, 0], minlength=n)
              + np.bincount(flat[:, 1], minlength=n))
    return place_state(mesh, SpmdState(
        edge_part=np.full(masks.shape, -1, np.int32),
        vparts=empty_vparts(n, cfg),
        degree_rest=degree.astype(np.int32),
        edges_per_part=np.zeros((p_num,), np.int32),
        key=jax.random.PRNGKey(cfg.seed),
        rounds=np.zeros((), np.int32),
        remaining=np.int32(flat.shape[0]),
    ))


@partial(jax.jit, static_argnames=("cfg", "limit", "n", "mesh"),
         out_shardings=STATE_SPECS)
def spmd_round_step(cfg: NEConfig, limit: int, n: int, mesh,
                    u_sh: Array, v_sh: Array, mask_sh: Array,
                    state: SpmdState) -> SpmdState:
    """One paper round as its own shard_map program.

    Exactly the traced round function the whole-run while_loop uses
    (:func:`_spmd_round`), so driving rounds one jit call at a time — and
    pausing/snapshotting/resuming between them — is bit-identical to the
    fire-and-forget :func:`partition_spmd` (asserted by
    tests/test_runtime.py).  ``state.edge_part`` is (D, C) and sharded over
    the device axis; everything else is replicated (:data:`STATE_SPECS`).
    The state comes back in that layout too, so round k's output is round
    k+1's input with no reshard and no second trace (left to itself, jit
    returns one device's ``vparts`` sharded like ``edge_part``).  So call it
    under ``jax.set_mesh(mesh)``, which the output specs are read against.
    """
    num_dev = mesh.shape[AXIS]

    def body(u_l, v_l, mask_l, ep_l, vp, dr, epp, key, rounds, remaining):
        st = SpmdState(ep_l[0], vp, dr, epp, key, rounds, remaining)
        out = _spmd_round(cfg, limit, n, num_dev, u_l[0], v_l[0],
                          mask_l[0], st)
        return out._replace(edge_part=out.edge_part[None])

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None),) * 3 + tuple(STATE_SPECS),
        out_specs=STATE_SPECS,
        check_vma=False,
    )(u_sh, v_sh, mask_sh, *state)


@partial(jax.jit, static_argnames=("p_num",))
def _quality_reduce(vparts: Array, degree_rest: Array, p_num: int):
    """The (P,)-and-scalar reduction behind the live quality gauges.

    One fused pass over the replicated replica map: per-partition replica
    counts |V(E_p)| and the boundary-set size (vertices already replicated
    somewhere but still carrying unallocated degree — the frontier the
    next round's two-hop allocation expands from).  Packed
    (uint32-word) replica sets unpack inside the jit, exactly as the
    round itself does for selection, so the gauge is cheap relative to a
    round on either representation.  No collectives: under multihost the
    inputs are fully replicated, so every worker computes the identical
    answer locally and no global state is ever gathered.
    """
    if vparts.dtype == jnp.uint32:
        vparts = ne_ops.unpack_bits(vparts, p_num)
    vrep = jnp.sum(vparts, axis=0, dtype=jnp.int32)              # (P,)
    boundary = jnp.sum(vparts.any(axis=1) & (degree_rest > 0),
                       dtype=jnp.int32)
    return vrep, boundary


def round_quality(cfg: NEConfig, state: SpmdState, n: int) -> dict:
    """Live quality gauges from a round state.

    Same math as :func:`repro.core.metrics.stats_from_counts` over the
    current replica/edge counts — so at the fixed point (no leftover
    edges) the live values equal the finalized artifact's metrics, which
    the multihost integration checks assert to 1e-6.
    """
    vrep_d, boundary = _quality_reduce(
        state.vparts, state.degree_rest, cfg.num_partitions)
    vrep = np.asarray(vrep_d, np.int64)
    counts = np.asarray(state.edges_per_part, np.int64)
    rf = float(vrep.sum()) / float(max(n, 1))
    eb = float(counts.max()) / max(float(counts.mean()), 1e-9)
    vb = float(vrep.max()) / max(float(vrep.mean()), 1e-9)
    return {"rf": rf, "eb": eb, "vb": vb, "boundary": int(boundary)}


def round_sync_payload_bytes(cfg: NEConfig, n: int, num_dev: int) -> int:
    """Per-device bytes one round's SyncVertexAllocations moves.

    The round-loop telemetry counter (``repro.obs``) and the ``round``
    span's argument: each ``_apply_alloc`` all-reduces the replica-set
    delta — an (N, P) int32 psum, or under ``cfg.use_pallas`` the
    (N, ⌈P/32⌉) uint32 words through ``compat.or_all_reduce``, which
    sends them once per recursive-doubling step (log2 D of them) or
    all-gathers D rows where D is no power of two — plus the (P,) count
    and (N,) D_rest deltas; the two-hop pass adds a second sync and the
    (D, P) quota histograms.  Each collective counts its operand (an
    all-gather its result), as in the compiled round
    (tests/test_tpu_compile.py); on one device, where the collectives
    compile away, it counts their operands all the same (the packed words
    once).  A pure function of the config so the driver can record it per
    round without touching device state.
    """
    p = cfg.num_partitions
    if cfg.use_pallas:
        d = max(num_dev, 2)
        sends = (d - 1).bit_length() if d & (d - 1) == 0 else d
        vbytes = n * ne_ops.replica_words(p) * 4 * sends
    else:
        vbytes = n * p * 4
    per_sync = vbytes + p * 4 + n * 4
    syncs = 2 if cfg.two_hop else 1
    gather = num_dev * p * 4 if cfg.two_hop else 0
    return syncs * per_sync + gather


def stitch_edge_part(ep_sh: np.ndarray, dev: np.ndarray, m: int,
                     ) -> np.ndarray:
    """Shard-order assignments back to global edge order: shard d holds
    ``edges[dev == d]`` in their original relative order.

    This whole-layout form allocates the O(M) output and is only for
    single-controller runs and explicit (lazy) materialization; the
    sharded multi-controller epilogue uses the slice-local
    ``repro.core.epilogue.stitch_slices`` it is built on, scattering one
    owned shard at a time into a caller-owned buffer.
    """
    edge_part = np.full((m,), -1, np.int32)
    ep_sh = np.asarray(ep_sh)
    eids = {dd: np.flatnonzero(dev == dd) for dd in range(ep_sh.shape[0])}
    return stitch_slices(edge_part, {dd: ep_sh[dd] for dd in eids}, eids)


@partial(jax.jit, static_argnames=("cfg", "limit", "n", "mesh"))
def _partition_spmd_jit(cfg: NEConfig, limit: int, n: int, mesh,
                        u_sh: Array, v_sh: Array, mask_sh: Array,
                        init: SpmdState) -> SpmdState:
    """Every round to the fixed point in one program: :func:`_spmd_round`
    under a while_loop, from ``init`` (:func:`spmd_init_state`), in and out
    in the layout of :data:`STATE_SPECS`."""
    num_dev = mesh.shape[AXIS]

    def body(u_l, v_l, mask_l, ep_l, *rest):
        def cond(s: SpmdState):
            return (s.remaining > 0) & (s.rounds < cfg.max_rounds)

        out = jax.lax.while_loop(
            cond,
            partial(_spmd_round, cfg, limit, n, num_dev, u_l[0], v_l[0],
                    mask_l[0]),
            SpmdState(ep_l[0], *rest))
        return out._replace(edge_part=out.edge_part[None])

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None),) * 3 + tuple(STATE_SPECS),
        out_specs=STATE_SPECS,
        check_vma=False,
    )(u_sh, v_sh, mask_sh, *init)


def run_rounds(cfg: NEConfig, limit: int, n: int, mesh, shards: np.ndarray,
               masks: np.ndarray, init: SpmdState) -> SpmdState:
    """Every round to the fixed point over the (D, C) edge ``shards`` and
    ``masks``, placed on ``mesh``, from ``init``; returns once the device
    is done."""
    return jax.block_until_ready(_partition_spmd_jit(
        cfg, limit, n, mesh, shard_over(mesh, shards[:, :, 0]),
        shard_over(mesh, shards[:, :, 1]), shard_over(mesh, masks), init))


def _shard_input(source, num_devices: int):
    """Edge shards + metadata from a Graph or a canonical EdgeFile.

    The EdgeFile path never builds a CSR: the SPMD partitioner only needs
    the raw edge shards, so a store handle goes disk → padded shards in two
    block passes (``repro.io.stream.shard_edges_stream``) — this is the
    §7-scale memory win of running straight from the store.
    """
    if isinstance(source, Graph):
        edges = np.asarray(source.edges)
        n, m = source.num_vertices, source.num_edges
        shards, masks, _, dev = shard_edges(edges, num_devices)
        return n, m, edges, shards, masks, dev
    if not isinstance(source, EdgeFile):
        raise TypeError(f"partition_spmd takes a Graph or an EdgeFile, "
                        f"got {type(source).__name__}")
    require_canonical(source)
    n, m = int(source.num_vertices), int(source.num_edges)
    shards, masks, _, dev, edges = shard_edges_stream(source, num_devices,
                                                      with_edges=True)
    return n, m, edges, shards, masks, dev


def partition_spmd(g: Graph, cfg: NEConfig,
                   num_devices: int | None = None) -> PartitionResult:
    """Run Distributed NE as an SPMD program over 2D-hash edge shards.

    ``g`` may be an in-memory Graph or a canonical ``repro.io.EdgeFile``
    (partitioned straight from the store, no CSR materialization).
    Returns a host-side :class:`PartitionResult`.
    """
    if compat.process_env()[1] > 1:
        raise RuntimeError(
            "partition_spmd is single-controller: it assembles the full "
            "shard layout in one process.  Multi-process jobs drive "
            "spmd_round_step through repro.runtime.PartitionDriver "
            "(scripts/launch_multihost.py), where each process ingests "
            "only its own host block range.")
    d = num_devices or len(jax.devices())
    d = max(1, min(d, len(jax.devices())))
    n, m, edges, shards, masks, dev = _shard_input(g, d)
    cfg = cfg.clamped(n)
    p_num = cfg.num_partitions
    if m == 0:
        return PartitionResult(np.zeros((0,), np.int32),
                               np.zeros((n, p_num), bool),
                               np.zeros((p_num,), np.int32), 0, 0)

    mesh = make_edge_mesh(d)
    limit = alpha_limit(cfg.alpha, m, p_num)
    st = run_rounds(cfg, limit, n, mesh, shards, masks,
                    spmd_init_state(shards, masks, n, cfg, mesh))

    edge_part = stitch_edge_part(st.edge_part, dev, m)
    vparts = np.asarray(st.vparts)
    if cfg.use_pallas:  # result surface is always (N, P) bool
        vparts = ne_ops.unpack_bits_np(vparts, p_num)
    return finalize_result(edge_part, vparts, st.edges_per_part, edges, cfg,
                           int(st.rounds))


def partition(g, cfg: NEConfig) -> PartitionResult:
    """Run Distributed NE on one device: :func:`partition_spmd` over a
    one-device mesh.  Returns the host-side result with cleanup applied.

    ``g`` is a Graph or a canonical ``repro.io.EdgeFile`` (partitioned
    straight from the store); any other handle ``core.graph.as_graph``
    accepts (a PackedCSR, a non-canonical EdgeFile) is materialized first.
    """
    if not (isinstance(g, Graph)
            or isinstance(g, EdgeFile) and g.canonical):
        g = as_graph(g)
    return partition_spmd(g, cfg, num_devices=1)
