"""Sparse embedding tables + EmbeddingBag for recsys.

JAX has no native EmbeddingBag or CSR sparse — the lookup is built from
``jnp.take`` + ``jax.ops.segment_sum`` as the assignment requires.  Two
paths:

  dense:  single-device gather (smoke tests, small tables).
  spmd:   tables row-sharded over the TP ("model") axis via shard_map —
          each shard gathers the ids in its row range and the partial
          results are psum-combined (ids outside the range contribute 0).
          Wire bytes per lookup batch = B·F·dim — the classic row-sharded
          embedding exchange; the all_to_all variant is a §Perf lever.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.context import get_mesh_ctx

Array = jax.Array


def embedding_bag_dense(table: Array, ids: Array, offsets: Array | None
                        = None, weights: Array | None = None,
                        mode: str = "sum") -> Array:
    """torch.nn.EmbeddingBag semantics.

    table: (V, D); ids: (K,) flat indices; offsets: (B+1,) bag boundaries
    (ids[offsets[i]:offsets[i+1]] form bag i).  offsets=None → (B, K) ids
    with one bag per row.
    """
    if offsets is None:
        emb = table[ids]                     # (B, K, D)
        if weights is not None:
            emb = emb * weights[..., None]
        out = emb.sum(axis=1)
        if mode == "mean":
            out = out / ids.shape[1]
        return out
    k = ids.shape[0]
    b = offsets.shape[0] - 1
    seg = jnp.searchsorted(offsets[1:], jnp.arange(k), side="right")
    emb = table[ids]
    if weights is not None:
        emb = emb * weights[:, None]
    out = jax.ops.segment_sum(emb, seg, num_segments=b)
    if mode == "mean":
        cnt = jax.ops.segment_sum(jnp.ones((k,)), seg, num_segments=b)
        out = out / jnp.maximum(cnt[:, None], 1.0)
    return out


def sharded_lookup(table: Array, ids: Array) -> Array:
    """(V, D) table × (..., ) ids → (..., D), row-sharded over model axis.

    Falls back to a plain gather without a mesh context.
    """
    ctx = get_mesh_ctx()
    if ctx is None:
        return table[ids]

    from jax.sharding import PartitionSpec as P

    import numpy as np

    mesh = ctx.mesh
    tp = mesh.shape[ctx.model_axis]
    v = table.shape[0]
    assert v % tp == 0, "table rows must divide the TP axis"
    v_local = v // tp
    dp = int(np.prod([mesh.shape[a] for a in ctx.batch_axes]))
    ba = ctx.batch_axes if ids.shape[0] % dp == 0 else ()  # batch=1 serve
    bspec = P(ba, *([None] * (ids.ndim - 1)))

    def body(tab, idx):
        r = jax.lax.axis_index(ctx.model_axis)
        lo = r * v_local
        local = idx - lo
        hit = (local >= 0) & (local < v_local)
        emb = tab[jnp.clip(local, 0, v_local - 1)]
        emb = jnp.where(hit[..., None], emb, 0.0)
        return jax.lax.psum(emb, ctx.model_axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(ctx.model_axis, None), bspec),
        out_specs=P(ba, *([None] * ids.ndim)),
    )(table, ids)
