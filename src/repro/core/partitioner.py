"""Distributed Neighbor Expansion (Distributed NE) — vectorized JAX core.

Implements the paper's parallel expansion (§3), distributed edge allocation
(§4) and multi-expansion (§5) as a bounded-shape, jit-compiled fixed-point
iteration.  One ``jax.lax.while_loop`` step == one paper round:

  1. every active partition selects its ``k = clamp(λ·|B_p|, 1, K)``
     minimum-``D_rest`` boundary vertices (priority queue → masked top_k);
     empty boundaries re-seed from a random vertex with unallocated edges,
  2. one-hop allocation with deterministic vertex-grain conflict resolution
     (min ``(edges_per_part, partition_id)`` key — the paper's CAS made
     reproducible; see docs/DESIGN-dist.md, ``partitioner_sm`` step 1),
  3. replica-set updates (the paper's ``SyncVertexAllocations`` — a no-op
     here because the single-controller state is already global; the
     shard_map version in ``repro.dist.partitioner_sm`` does the OR
     all-reduce),
  4. two-hop "free edge" allocation under Condition (5) with
     ``argmin NumEdges`` tie-breaking (paper Alg. 3).

Boundary sets are *derived*, not stored: ``v ∈ B_p  ⇔  p ∈ parts(v) ∧
D_rest(v) > 0`` — this is exactly the paper's definition of B(X) and avoids
an (N, P) frontier structure.

Each phase of a round runs under a ``jax.named_scope`` — ``ne_select``,
``ne_one_hop``, ``ne_sync``, ``ne_two_hop`` — shared by the single, hybrid
and SPMD rounds, so a profiler trace attributes every op to its phase
(the scope is the op's HLO ``op_name``; results are unchanged).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.epilogue import (alpha_limit, cleanup_leftovers,  # noqa: F401 — re-exported epilogue surface
                                 leftover_plan, leftover_targets)
from repro.core.graph import (Graph, as_graph, exclusive_rank,
                              target_histogram)
from repro.core.metrics import stats_from_counts
from repro.kernels.ne_round import ops as ne_ops

Array = jax.Array
I32_INF = np.iinfo(np.int32).max


@dataclasses.dataclass(frozen=True)
class NEConfig:
    """Distributed NE hyper-parameters (paper defaults)."""

    num_partitions: int
    alpha: float = 1.1          # imbalance factor (paper §7.1)
    lam: float = 0.1            # expansion factor λ (paper §5, Fig. 6)
    k_sel: int = 256            # static cap on per-round selections per part
    max_rounds: int = 4096      # safety bound on while_loop
    sel_chunk: int = 8          # partitions scored per selection chunk
    edge_chunk: int = 1 << 18   # edges per two-hop intersection chunk
    two_hop: bool = True        # Condition (5) allocation on/off (ablation)
    seed: int = 0
    # Bit-packed replica sets (the Pallas ne_round packing kernels) in the
    # SPMD partitioner; the single-controller round ignores it.  None
    # resolves from the REPRO_NE_KERNELS env var at construction, so a
    # resolved config is self-contained and its snapshot fingerprint
    # stable.  Both values produce bit-identical results (asserted in tests).
    use_pallas: bool = None

    def __post_init__(self):
        assert self.num_partitions >= 1
        assert self.alpha > 1.0
        assert 0.0 < self.lam <= 1.0
        if self.use_pallas is None:
            object.__setattr__(self, "use_pallas", ne_ops.env_enabled())

    def clamped(self, num_vertices: int) -> "NEConfig":
        return dataclasses.replace(self, k_sel=min(self.k_sel, num_vertices))


class NEState(NamedTuple):
    edge_part: Array        # (M,)   int32, -1 = unallocated
    vparts: Array           # (N, P) bool replica sets  V(E_p)
    degree_rest: Array      # (N,)   int32  D_rest
    edges_per_part: Array   # (P,)   int32  |E_p|
    key: Array              # PRNG key
    rounds: Array           # ()     int32
    new_last_round: Array   # ()     int32  edges allocated in last round


class PartitionResult:
    """Final output of a partitioning run.

    Fields: ``edge_part`` (M,) int32 final assignment, ``vparts`` (N, P)
    bool replica sets, ``edges_per_part`` (P,) int32, ``rounds``,
    ``leftover`` (edges assigned by the cleanup pass), and optional
    ``stats`` (:class:`repro.core.metrics.PartitionStats`, filled by the
    finalize epilogue from the replica/edge counts).

    ``edge_part`` may be passed as a zero-argument callable: the sharded
    multi-controller epilogue hands back a *lazy* assignment so that no
    host materializes the O(M) global array unless a consumer explicitly
    asks for it — intended for small graphs and tests; production
    consumers read the per-partition artifact shards and ``stats``
    instead.  Materialization is cached.
    """

    __slots__ = ("_edge_part", "vparts", "edges_per_part", "rounds",
                 "leftover", "stats")

    def __init__(self, edge_part, vparts, edges_per_part, rounds, leftover,
                 stats=None):
        self._edge_part = edge_part
        self.vparts = vparts
        self.edges_per_part = edges_per_part
        self.rounds = rounds
        self.leftover = leftover
        self.stats = stats

    @property
    def edge_part(self) -> np.ndarray:
        if callable(self._edge_part):
            self._edge_part = self._edge_part()
        return self._edge_part

    @property
    def edge_part_materialized(self) -> bool:
        """False while a lazy assignment has not been forced yet."""
        return not callable(self._edge_part)


def priority_enc(count: Array, p: Array, num_partitions: int) -> Array:
    """Priority key: smaller edge count wins, then smaller partition id."""
    cap = (I32_INF - num_partitions) // num_partitions - 1
    return jnp.minimum(count, cap) * num_partitions + p


def select_chunk(vparts_c, active_c, degree_rest, lam, k_sel, keys_c,
                 remaining_c):
    """Selection for a chunk of partitions.  vparts_c: (C, N) bool."""
    bnd = vparts_c & (degree_rest > 0)[None, :] & active_c[:, None]   # (C,N)
    bsize = bnd.sum(axis=1)                                            # (C,)
    # k_eff = clamp(ceil(λ|B_p|), 1, K)   (paper Alg. 4 line 5)
    k_eff = jnp.clip(jnp.ceil(lam * bsize).astype(jnp.int32), 1, k_sel)
    scores = jnp.where(bnd, degree_rest[None, :], I32_INF)
    neg_top, idx = jax.lax.top_k(-scores, k_sel)                       # (C,K)
    valid = (neg_top > -I32_INF) & (jnp.arange(k_sel)[None, :] < k_eff[:, None])
    # Capacity-aware prefix: D_rest(v) is exactly the one-hop edge cost of
    # expanding v (paper Eq. 3) — keep only the selection prefix that fits
    # the partition's remaining α-capacity (the paper's per-round overshoot
    # is one vertex; multi-expansion must not multiply it by k).
    cost = jnp.where(valid, -neg_top, 0)
    fits = jnp.cumsum(cost, axis=1) <= remaining_c[:, None]
    valid &= fits | (jnp.arange(k_sel)[None, :] == 0)
    # Random re-seed when the boundary is empty (paper Alg. 1 line 6).
    any_rest = degree_rest > 0
    gumb = jax.vmap(lambda k: jax.random.uniform(k, degree_rest.shape))(
        keys_c)
    rnd_v = jnp.argmax(jnp.where(any_rest[None, :], gumb, -1.0), axis=1)
    restart = (bsize == 0) & active_c & any_rest.any()
    first = jnp.where(restart, rnd_v.astype(jnp.int32), idx[:, 0])
    idx = idx.at[:, 0].set(first)
    valid = valid.at[:, 0].set(jnp.where(restart, True, valid[:, 0]))
    valid &= active_c[:, None]
    return idx, valid


@jax.named_scope("ne_select")
def vertex_claims(cfg: NEConfig, limit: int, vparts: Array,
                  degree_rest: Array, edges_per_part: Array,
                  sub: Array) -> Array:
    """Selection (multi-expansion §5) + vertex-grain claims (Alg. 3).

    Pure function of the *global* round state — the SPMD partitioner calls
    it with replicated state so every device derives identical claims.
    Returns (N,) int32 claim keys: ``priority_enc(|E_p|, p)`` for claimed
    vertices, ``I32_INF`` where no partition claimed the vertex.
    """
    n = vparts.shape[0]
    p_num = cfg.num_partitions
    active = edges_per_part <= limit                # soft cap (paper Alg. 1)

    # --- selection (multi-expansion, paper §5) -----------------------------
    c = min(cfg.sel_chunk, p_num)
    n_chunks = (p_num + c - 1) // c
    p_pad = n_chunks * c
    part_ids = jnp.arange(p_pad, dtype=jnp.int32)
    keys = jax.vmap(lambda i: jax.random.fold_in(sub, i))(part_ids)
    vparts_pad = jnp.pad(vparts, ((0, 0), (0, p_pad - p_num)))
    active_pad = jnp.pad(active, (0, p_pad - p_num))

    remaining = jnp.pad(limit - edges_per_part, (0, p_pad - p_num))

    def sel(args):
        pc, ac, kc, rc = args
        return select_chunk(pc, ac, degree_rest, cfg.lam, cfg.k_sel, kc, rc)

    sel_idx, sel_valid = jax.lax.map(
        sel,
        (vparts_pad.reshape(n, n_chunks, c).transpose(1, 2, 0),
         active_pad.reshape(n_chunks, c),
         keys.reshape(n_chunks, c, *keys.shape[1:]),
         remaining.reshape(n_chunks, c)),
    )
    sel_idx = sel_idx.reshape(p_pad, cfg.k_sel)[:p_num]
    sel_valid = sel_valid.reshape(p_pad, cfg.k_sel)[:p_num]

    # --- vertex-grain claims (paper Alg. 3) --------------------------------
    part_of_row = jnp.broadcast_to(
        jnp.arange(p_num, dtype=jnp.int32)[:, None], sel_idx.shape)
    claim_keys = priority_enc(edges_per_part[part_of_row.ravel()],
                              part_of_row.ravel(), p_num)
    flat_v = jnp.where(sel_valid.ravel(), sel_idx.ravel(), n)   # n → dropped
    vclaim_key = jnp.full((n,), I32_INF, jnp.int32)
    return vclaim_key.at[flat_v].min(claim_keys, mode="drop")


@jax.named_scope("ne_one_hop")
def one_hop(vclaim: Array, u: Array, v: Array, edge_part: Array,
            num_partitions: int, mask: Array | None = None):
    """One-hop allocation (paper Alg. 3) over an edge list.

    Per edge: ``k = min(vclaim[u], vclaim[v])``; an unallocated edge
    (``mask`` false → never) joins partition ``k % P`` when some endpoint
    was claimed — the min over the edge's two directed CSR slots, in one
    pass over M edges.  Returns ``(part, counts)``: (M,) int32, ``-1``
    for untouched edges, and the (P,) int32 histogram of new allocations
    (``target_histogram``: M·P compare-adds, no scatter).
    """
    k_uv = jnp.minimum(vclaim[u], vclaim[v])
    new = (edge_part < 0) & (k_uv < I32_INF)
    if mask is not None:
        new &= mask
    part = jnp.where(new, (k_uv % num_partitions).astype(jnp.int32), -1)
    return part, target_histogram(part, num_partitions)


def _round(g: Graph, cfg: NEConfig, limit: int, state: NEState) -> NEState:
    n = g.num_vertices
    m = g.num_edges
    p_num = cfg.num_partitions
    with jax.named_scope("ne_select"):
        key, sub = jax.random.split(state.key)

    vclaim_key = vertex_claims(cfg, limit, state.vparts, state.degree_rest,
                               state.edges_per_part, sub)

    # --- one-hop allocation ------------------------------------------------
    u, v = g.edges[:, 0], g.edges[:, 1]
    part1, counts1 = one_hop(vclaim_key, u, v, state.edge_part, p_num)
    with jax.named_scope("ne_one_hop"):
        new1 = part1 >= 0
        edge_part = jnp.where(new1, part1, state.edge_part)

    # --- replica-set update (the SPMD round's SyncVertexAllocations) -------
    with jax.named_scope("ne_sync"):
        add_row = jnp.where(new1, part1, 0)
        vparts = state.vparts
        drop_u = jnp.where(new1, u, n)
        drop_v = jnp.where(new1, v, n)
        vparts = vparts.at[drop_u, add_row].set(True, mode="drop")
        vparts = vparts.at[drop_v, add_row].set(True, mode="drop")
        dec = (jnp.zeros((n,), jnp.int32)
               .at[drop_u].add(new1.astype(jnp.int32), mode="drop")
               .at[drop_v].add(new1.astype(jnp.int32), mode="drop"))
        degree_rest = state.degree_rest - dec
        edges_per_part = state.edges_per_part + counts1

    # --- 3. two-hop "free edge" allocation, Condition (5) ------------------
    if cfg.two_hop:
        with jax.named_scope("ne_two_hop"):
            ce = min(cfg.edge_chunk, m)
            n_ec = (m + ce - 1) // ce
            m_pad = n_ec * ce
            pad = m_pad - m
            u_p = jnp.pad(u, (0, pad))
            v_p = jnp.pad(v, (0, pad))
            un_p = jnp.pad(edge_part < 0, (0, pad))  # pads → False
            # tie-break by |E_p| (Alg. 3 line 16)
            enc_vec = priority_enc(edges_per_part,
                                   jnp.arange(p_num, dtype=jnp.int32), p_num)
            # free edges only go to partitions still under the α-capacity,
            # and a partition may absorb at most its remaining capacity this
            # round — otherwise one round's free-edge batch around a hub
            # blows up |E_p| (the paper's per-vertex expansion granularity
            # implies the same cap).
            enc_vec = jnp.where(edges_per_part <= limit, enc_vec, I32_INF)
            quota0 = jnp.maximum(limit + 1 - edges_per_part, 0)

            def two_hop(quota, args):
                uu, vv, unal = args
                inter = vparts[uu] & vparts[vv]                  # (ce, P)
                k2 = jnp.where(inter & unal[:, None], enc_vec[None, :],
                               I32_INF)
                best = k2.min(axis=1)
                cand = jnp.where(best < I32_INF, best % p_num, -1)
                rank = exclusive_rank(cand, p_num)
                keep = (cand >= 0) & (rank < quota[jnp.maximum(cand, 0)])
                out = jnp.where(keep, cand, -1)
                return quota - target_histogram(out, p_num), out

            _, part2 = jax.lax.scan(
                two_hop, quota0,
                (u_p.reshape(n_ec, ce), v_p.reshape(n_ec, ce),
                 un_p.reshape(n_ec, ce)),
            )
            part2 = part2.reshape(m_pad)[:m]
            new2 = part2 >= 0
            edge_part = jnp.where(new2, part2, edge_part)
            edges_per_part = edges_per_part + target_histogram(part2, p_num)
            dec2 = (jnp.zeros((n,), jnp.int32)
                    .at[jnp.where(new2, u, n)].add(new2.astype(jnp.int32),
                                                   mode="drop")
                    .at[jnp.where(new2, v, n)].add(new2.astype(jnp.int32),
                                                   mode="drop"))
            degree_rest = degree_rest - dec2
            new_total = new1.sum() + new2.sum()
    else:
        new_total = new1.sum()

    return NEState(edge_part, vparts, degree_rest, edges_per_part, key,
                   state.rounds + 1, new_total.astype(jnp.int32))


def _init_state(g: Graph, cfg: NEConfig) -> NEState:
    n, m, p = g.num_vertices, g.num_edges, cfg.num_partitions
    return NEState(
        edge_part=jnp.full((m,), -1, jnp.int32),
        vparts=jnp.zeros((n, p), bool),
        degree_rest=g.degree.astype(jnp.int32),
        edges_per_part=jnp.zeros((p,), jnp.int32),
        key=jax.random.PRNGKey(cfg.seed),
        rounds=jnp.zeros((), jnp.int32),
        new_last_round=jnp.ones((), jnp.int32),
    )


# Round-stepping surface for the checkpointable runtime
# (``repro.runtime.driver``): one jit call == one paper round, on exactly
# the traced round function the whole-run while_loop uses — which is what
# makes pause/snapshot/resume bit-identical to an uninterrupted run.
ne_init_state = jax.jit(_init_state, static_argnames=("cfg",))
ne_round_step = jax.jit(_round, static_argnames=("cfg", "limit"))


def ne_done(state: NEState, cfg: NEConfig) -> bool:
    """Host-side mirror of the whole-run while_loop condition."""
    return bool((np.asarray(state.edge_part) >= 0).all()
                or int(state.rounds) >= cfg.max_rounds)


@partial(jax.jit, static_argnames=("cfg",))
def _partition_jit(g: Graph, cfg: NEConfig) -> NEState:
    limit = alpha_limit(cfg.alpha, g.num_edges, cfg.num_partitions)
    init = _init_state(g, cfg)

    def cond(s: NEState):
        return ((s.edge_part < 0).any()
                & (s.rounds < cfg.max_rounds))

    return jax.lax.while_loop(cond, partial(_round, g, cfg, limit), init)


def finalize_result(edge_part, vparts, counts, edges: np.ndarray,
                    cfg: NEConfig, rounds: int) -> PartitionResult:
    """Host-side epilogue shared by every single-controller entry point:
    copy the device state (asarray views of jax arrays are read-only, the
    cleanup pass mutates in place), water-fill the max_rounds leftovers
    (``repro.core.epilogue``), attach the quality stats, wrap.

    The multi-controller driver runs the same epilogue *per shard slice*
    (``repro.runtime.finalize``) — this whole-array form is the small
    graph / test path.
    """
    edge_part = np.array(edge_part)
    vparts = np.array(vparts)
    counts = np.array(counts)
    limit = alpha_limit(cfg.alpha, edges.shape[0], cfg.num_partitions)
    leftover = cleanup_leftovers(edge_part, vparts, counts, edges,
                                 cfg.num_partitions, limit)
    stats = stats_from_counts(vparts.sum(axis=0), counts, vparts.shape[0])
    return PartitionResult(edge_part, vparts, counts, int(rounds), leftover,
                           stats)


def partition(g: Graph, cfg: NEConfig) -> PartitionResult:
    """Run Distributed NE.  Returns host-side result with cleanup applied.

    ``g`` may be a Graph or any store handle ``core.graph.as_graph``
    accepts (EdgeFile, PackedCSR) — this path needs the full CSR, so store
    inputs are materialized via the streaming builder first.
    """
    g = as_graph(g)
    cfg = cfg.clamped(g.num_vertices)
    state = jax.block_until_ready(_partition_jit(g, cfg))
    return finalize_result(state.edge_part, state.vparts,
                           state.edges_per_part, np.asarray(g.edges), cfg,
                           int(state.rounds))
