"""All-to-all edge redistribution: partition p's edges land on device p.

After ``partition_spmd`` finishes, edges still live where the 2D-hash
initial distribution put them.  The GAS engine (``apps.engine``) wants
device ``d`` to own partition ``d``'s edges.  ``redistribute_edges`` is
the one-shot ``all_to_all`` shuffle between the two layouts — the paper's
final edge-migration step, and the hand-off that feeds
``apps.engine.build_sharded_graph``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.graph import exclusive_rank
from repro.dist import compat

AXIS = "shard"


def _redistribute_numpy(shards, parts, valid, cap):
    """Reference path (also used when fewer devices than shards exist)."""
    d = valid.shape[0]
    edges_out = np.zeros((d, d * cap, 2), np.int32)
    mask_out = np.zeros((d, d * cap), bool)
    for dst in range(d):
        for src in range(d):
            rows = shards[src][valid[src] & (parts[src] == dst)]
            lo = src * cap
            edges_out[dst, lo: lo + rows.shape[0]] = rows
            mask_out[dst, lo: lo + rows.shape[0]] = True
    return edges_out, mask_out


def redistribute_edges(shards: np.ndarray, masks: np.ndarray,
                       parts: np.ndarray
                       ) -> tuple[np.ndarray, np.ndarray, int]:
    """Shuffle edge rows so partition ``p``'s edges land on device ``p``.

    Args:
      shards: (D, C, 2) int32 edge endpoints, one row per shard slot.
      masks:  (D, C) bool — valid rows.
      parts:  (D, C) int32 target partition per row (read where mask set).

    Returns ``(edges_out, mask_out, dropped)``: ``edges_out`` is
    (D, D*cap, 2) int32 where block ``s`` of device ``p``'s axis holds the
    rows received from source shard ``s`` (original relative order
    preserved); ``mask_out`` marks valid rows; ``dropped`` counts masked
    rows whose target partition fell outside [0, D).
    """
    shards = np.asarray(shards, np.int32)
    masks = np.asarray(masks, bool)
    parts = np.asarray(parts, np.int32)
    d, _ = masks.shape
    valid = masks & (parts >= 0) & (parts < d)
    dropped = int(masks.sum() - valid.sum())

    # static send capacity per (source, target) stream
    counts = np.zeros((d, d), np.int64)
    for dd in range(d):
        if valid[dd].any():
            counts[dd] = np.bincount(parts[dd][valid[dd]], minlength=d)
    cap = max(1, int(counts.max()))

    if len(jax.devices()) < d:
        edges_out, mask_out = _redistribute_numpy(shards, parts, valid, cap)
        return edges_out, mask_out, dropped

    mesh = compat.make_mesh((d,), (AXIS,))
    # pack (u, v, target, valid) per slot so one all_to_all moves everything
    packed = np.concatenate(
        [shards, parts[:, :, None], valid[:, :, None].astype(np.int32)],
        axis=2).astype(np.int32)                               # (D, C, 4)

    def body(rows_l):
        rows_l = rows_l[0]                                     # (C, 4)
        uv = rows_l[:, :2]
        tgt = jnp.where(rows_l[:, 3] > 0, rows_l[:, 2], -1)
        # stable slotting: rank within this device's per-target stream
        myrank = exclusive_rank(tgt, d)
        slot = jnp.where(tgt >= 0, jnp.maximum(tgt, 0) * cap + myrank,
                         d * cap)                              # OOB → drop
        buf = jnp.zeros((d * cap, 2), jnp.int32).at[slot].set(uv,
                                                              mode="drop")
        ok = jnp.zeros((d * cap,), jnp.int32).at[slot].set(1, mode="drop")
        payload = jnp.concatenate([buf, ok[:, None]], axis=1)  # (D*cap, 3)
        got = jax.lax.all_to_all(payload.reshape(d, cap, 3), AXIS, 0, 0,
                                 tiled=True)
        return got.reshape(1, d * cap, 3)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(AXIS, None, None),
        out_specs=P(AXIS, None, None), check_vma=False))
    out = np.asarray(fn(jnp.asarray(packed)))                  # (D, D*cap, 3)
    return out[:, :, :2].astype(np.int32), out[:, :, 2] > 0, dropped
