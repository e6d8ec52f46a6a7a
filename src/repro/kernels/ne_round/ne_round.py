"""Pallas kernels for the bit-packed replica sets of the SPMD NE round.

``pack_bits`` / ``unpack_bits`` / ``or_words`` convert between the
(N, P) bool replica map and its (N, ceil(P/32)) uint32 packing, and
OR-merge two packings.  The SPMD partitioner uses them under
``NEConfig.use_pallas`` to shrink the per-round replica-map all-reduce
from (N, P)·4 bytes (int32 psum) to (N, ceil(P/32))·4 bytes.

The rest of the round (one-hop claims, boundary top-k, claim scatter-min)
runs as XLA (``repro.core.partitioner``): it needs a 1-D gather over an (N,) table,
``top_k`` and scatter-min, none of which Pallas TPU lowers.

Off-TPU everything runs under ``interpret=True`` (same pattern as
``block_spmm``), so CPU CI executes these kernel bodies for real; the
TPU compile of each is pinned by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ne_round.ref import replica_words

# rows x lanes of one tile: wider replica sets take fewer rows per grid
# step, so a tile's VMEM footprint stays flat in P
TILE_ELEMS = 4096 * 32
# Mosaic wants a block's row count to be a multiple of the sublane tile
# (32 rows for the 1-byte bool blocks) unless the block spans the array
ROW_ALIGN = 32


def _pack_kernel(b_ref, w_ref, *, w: int):
    b = b_ref[...]                                     # (TR, w*32)
    bits = jax.lax.broadcasted_iota(jnp.int32, (b.shape[0], 32), 1)
    # distinct powers of two never carry, so the int32 sum (Mosaic has no
    # unsigned reductions) wraps to exactly the OR of the bits; one static
    # 32-lane slice per word (Mosaic refuses the (TR, w, 32) reshape)
    words = jnp.concatenate(
        [(b[:, 32 * j:32 * (j + 1)].astype(jnp.int32) << bits)
         .sum(axis=-1, keepdims=True) for j in range(w)], axis=1)
    w_ref[...] = jax.lax.bitcast_convert_type(words, jnp.uint32)


def _unpack_kernel(w_ref, b_ref, *, w: int):
    words = w_ref[...]                                 # (TR, w)
    tr = words.shape[0]
    bits = jax.lax.broadcasted_iota(jnp.uint32, (tr, w, 32), 2)
    b_ref[...] = (((words[:, :, None] >> bits) & jnp.uint32(1))
                  .reshape(tr, w * 32).astype(jnp.bool_))


def _or_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] | b_ref[...]


def _row_tiles(n: int, w: int):
    tr = min(max(ROW_ALIGN, TILE_ELEMS // (32 * w) // ROW_ALIGN * ROW_ALIGN),
             n)
    n_pad = -(-n // tr) * tr
    return tr, n_pad


def pack_bits(bools, interpret: bool = True):
    """(N, P) bool → (N, ceil(P/32)) uint32, LSB-first (see ref.py)."""
    n, p = bools.shape
    w = replica_words(p)
    tr, n_pad = _row_tiles(n, w)
    bp = jnp.pad(bools, ((0, n_pad - n), (0, w * 32 - p)))
    words = pl.pallas_call(
        functools.partial(_pack_kernel, w=w),
        grid=(n_pad // tr,),
        in_specs=[pl.BlockSpec((tr, w * 32), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, w), jnp.uint32),
        interpret=interpret,
    )(bp)
    return words[:n]


def unpack_bits(words, num_partitions: int, interpret: bool = True):
    """(N, W) uint32 → (N, P) bool — inverse of ``pack_bits``."""
    n, w = words.shape
    tr, n_pad = _row_tiles(n, w)
    wp = jnp.pad(words, ((0, n_pad - n), (0, 0)))
    bools = pl.pallas_call(
        functools.partial(_unpack_kernel, w=w),
        grid=(n_pad // tr,),
        in_specs=[pl.BlockSpec((tr, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, w * 32), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, w * 32), jnp.bool_),
        interpret=interpret,
    )(wp)
    return bools[:n, :num_partitions]


def or_words(a, b, interpret: bool = True):
    """Element-wise OR of two packed replica maps."""
    n, w = a.shape
    tr, n_pad = _row_tiles(n, w)
    ap = jnp.pad(a, ((0, n_pad - n), (0, 0)))
    bp = jnp.pad(b, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        _or_kernel,
        grid=(n_pad // tr,),
        in_specs=[pl.BlockSpec((tr, w), lambda i: (i, 0)),
                  pl.BlockSpec((tr, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, w), jnp.uint32),
        interpret=interpret,
    )(ap, bp)
    return out[:n]
