"""Finalize epilogue kernels — deliberately jax-free.

The epilogue of every partitioning run (single-controller, SPMD, and the
true multi-controller driver) is host-side numpy: water-fill the
``max_rounds`` leftovers, stitch shard-order assignments back to edge
order, wrap the result.  In a multi-controller deployment each host runs
this *per shard slice* — the paper's space-efficiency headline (§7.3)
dies the moment any host materializes the O(M) global assignment, so the
sharded epilogue is split into

* :func:`leftover_plan` — the global water-fill split, a pure function of
  the replicated round state (|E_p| counts + the global leftover count),
  so every host computes the identical plan with no coordination;
* :func:`leftover_targets` — rank → partition lookup under a plan,
  without materializing the O(leftover) ``np.repeat`` expansion;
* :func:`finalize_local` — apply the plan to one shard slice (and the
  local replica-map copy) given the globally-agreed ranks of its
  leftover edges;
* :func:`stitch_slices` — the slice-local stitch: scatter one shard's
  slot-order assignments to their global edge ids (the caller owns the
  output buffer — only explicit materialization ever allocates it).

``cleanup_leftovers`` is the single-host composition of the same pieces,
bit-identical to the pre-split implementation (asserted by
tests/test_runtime.py).  This module must stay importable without jax:
the ``bench_memory`` finalize-RSS gate measures the epilogue in
numpy-only child processes, where the interpreter baseline would
otherwise drown the O(M)-vs-O(M/H) signal.
"""
from __future__ import annotations

import numpy as np


def alpha_limit(alpha: float, m: int, num_partitions: int) -> int:
    """α-capacity limit ``⌊α·|E|/|P|⌋`` (paper Alg. 1).

    The single shared definition for every enforcement site — the round
    (``dist.partitioner_sm``), the cleanup pass and the hybrid's global
    capacity all depend on the expression staying bit-identical.
    """
    return int(alpha * m / num_partitions)


def _waterfill(counts: np.ndarray, cap: np.ndarray, k: int) -> np.ndarray:
    """Per-partition takes for ``k`` unit increments, each going to the
    currently least-loaded partition with remaining capacity — the greedy
    computed in closed form (binary search on the fill level) instead of
    k sequential argmins.  Ties at the final level break by partition id.
    """
    take = np.zeros_like(counts)
    if k <= 0:
        return take

    def filled(level: int) -> int:
        return int(np.minimum(np.maximum(level - counts, 0), cap).sum())

    lo, hi = int(counts.min()), int(counts.max()) + k + 1
    while lo < hi:                  # largest level with filled(level) <= k
        mid = (lo + hi + 1) // 2
        if filled(mid) <= k:
            lo = mid
        else:
            hi = mid - 1
    take = np.minimum(np.maximum(lo - counts, 0), cap)
    spill = k - int(take.sum())
    if spill > 0:
        room = np.nonzero((take < cap) & (counts + take == lo))[0]
        take[room[:spill]] += 1
    return take


def leftover_plan(counts: np.ndarray, num_leftover: int,
                  num_partitions: int, limit: int) -> np.ndarray:
    """Global water-fill split of ``num_leftover`` unallocated edges.

    Leftovers fill the least-loaded partitions while they are under the
    α-capacity ``limit``; only when every partition is at capacity does
    the overflow water-fill freely (still least-loaded first), so balance
    degrades as slowly as possible.  Pure function of replicated state —
    every host of a sharded finalize derives the identical (P,) int64
    plan (summing to ``num_leftover``) with no coordination.
    """
    c64 = np.asarray(counts).astype(np.int64)
    free = np.maximum(limit - c64, 0)
    k_capped = min(int(num_leftover), int(free.sum()))
    take = _waterfill(c64, free, k_capped)
    overflow = int(num_leftover) - k_capped
    if overflow:
        no_cap = np.full(num_partitions, overflow, np.int64)
        take = take + _waterfill(c64 + take, no_cap, overflow)
    return take


def leftover_targets(take: np.ndarray, ranks: np.ndarray) -> np.ndarray:
    """Partition of each global leftover rank under plan ``take``.

    Equivalent to ``np.repeat(np.arange(P), take)[ranks]`` without the
    O(total-leftover) expansion — the sharded epilogue looks up only its
    own slice's ranks.
    """
    bounds = np.cumsum(np.asarray(take, np.int64))
    return np.searchsorted(bounds, np.asarray(ranks, np.int64),
                           side="right").astype(np.int32)


def finalize_local(ep_slice: np.ndarray, u_slice: np.ndarray,
                   v_slice: np.ndarray, ranks: np.ndarray,
                   take: np.ndarray, vparts: np.ndarray) -> int:
    """Per-shard half of the sharded finalize: fill this slice's leftover
    slots from the globally-agreed water-fill ``take`` and mark the new
    replicas in the local ``vparts`` copy, in place.

    ``ep_slice`` / ``u_slice`` / ``v_slice`` are the shard's *valid
    prefix* (no padding); ``ranks`` are the global eid-order ranks of its
    leftover edges, in slot order (slot order within a shard is eid
    order, so the caller's sorted-eid ranks line up directly).  Returns
    the number of edges assigned — every array touched here is O(slice),
    never O(M).
    """
    rem = np.flatnonzero(ep_slice < 0)
    if rem.size == 0:
        return 0
    tgt = leftover_targets(take, ranks)
    ep_slice[rem] = tgt
    vparts[u_slice[rem], tgt] = True
    vparts[v_slice[rem], tgt] = True
    return int(rem.size)


def cleanup_leftovers(edge_part: np.ndarray, vparts: np.ndarray,
                      counts: np.ndarray, edges: np.ndarray,
                      num_partitions: int, limit: int) -> int:
    """Assign unallocated edges (the max_rounds safety hatch), in place.

    The single-host composition of :func:`leftover_plan` +
    :func:`finalize_local`: the "slice" is the whole assignment and the
    global ranks are ``0..k-1`` in eid order.  Returns the number of
    edges assigned.
    """
    rem = np.nonzero(edge_part < 0)[0]
    if rem.size == 0:
        return 0
    take = leftover_plan(counts, int(rem.size), num_partitions, limit)
    tgt = leftover_targets(take, np.arange(rem.size, dtype=np.int64))
    edge_part[rem] = tgt
    counts += take.astype(counts.dtype)
    vparts[edges[rem, 0], tgt] = True
    vparts[edges[rem, 1], tgt] = True
    return int(rem.size)


def stitch_slices(out: np.ndarray, ep_slices: dict, eids: dict,
                  ) -> np.ndarray:
    """Slice-local stitch: scatter shard slot-order assignments to their
    global edge ids.

    ``ep_slices[d]`` is shard ``d``'s (possibly padded) assignment and
    ``eids[d]`` its global edge ids in slot order; only the valid prefix
    (``eids[d].size`` slots) is read.  The caller owns ``out`` — the
    sharded epilogue never allocates an (M,) buffer, only explicit
    materialization (lazy ``PartitionResult.edge_part``, the
    single-controller finalize) does.
    """
    for d, e in eids.items():
        out[e] = np.asarray(ep_slices[d])[: e.size]
    return out


__all__ = ["alpha_limit", "cleanup_leftovers", "finalize_local",
           "leftover_plan", "leftover_targets", "stitch_slices"]
