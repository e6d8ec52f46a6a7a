"""Distributed Neighbor Expansion (Distributed NE) — what the round shares.

The paper's parallel expansion (§3), distributed edge allocation (§4) and
multi-expansion (§5) run as one round program,
``repro.dist.partitioner_sm._spmd_round``, over 2D-hash edge shards; one
device is a one-device mesh.  ``partition``, ``partition_spmd``, the
hybrid's expansion and :class:`repro.runtime.PartitionDriver` all step it.
This module holds the pieces that round and the host epilogue share:

  * :class:`NEConfig` and :class:`PartitionResult`;
  * the round's phase 1, :func:`vertex_claims`: every active partition
    selects its ``k = clamp(λ·|B_p|, 1, K)`` minimum-``D_rest`` boundary
    vertices (priority queue → masked top_k, :func:`select_chunk`); empty
    boundaries re-seed from a random vertex with unallocated edges; claims
    resolve at vertex grain by the min ``(edges_per_part, partition_id)``
    key (:func:`priority_enc` — the paper's CAS made reproducible; see
    docs/DESIGN-dist.md);
  * its phase 2, :func:`one_hop` allocation over an edge list;
  * :func:`finalize_result`, the whole-array epilogue.

Boundary sets are *derived*, not stored: ``v ∈ B_p  ⇔  p ∈ parts(v) ∧
D_rest(v) > 0`` — this is exactly the paper's definition of B(X) and avoids
an (N, P) frontier structure.  The phases and their ``jax.named_scope``
names are listed in ``repro.dist.partitioner_sm``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.epilogue import (alpha_limit, cleanup_leftovers,  # noqa: F401 — re-exported epilogue surface
                                 leftover_plan, leftover_targets)
from repro.core.graph import target_histogram
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
    # round.  None resolves from the REPRO_NE_KERNELS env var at
    # construction, so a resolved config is self-contained and its
    # snapshot fingerprint stable.  Both values produce bit-identical
    # results (asserted in tests).
    use_pallas: bool = None

    def __post_init__(self):
        assert self.num_partitions >= 1
        assert self.alpha > 1.0
        assert 0.0 < self.lam <= 1.0
        if self.use_pallas is None:
            object.__setattr__(self, "use_pallas", ne_ops.env_enabled())

    def clamped(self, num_vertices: int) -> "NEConfig":
        return dataclasses.replace(self, k_sel=min(self.k_sel, num_vertices))


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
