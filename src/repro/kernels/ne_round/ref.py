"""Plain-XLA and numpy twins of the ne_round packing kernels.

The XLA functions are the oracle the Pallas kernels in ``ne_round.py``
must reproduce bit-for-bit (all-integer math — no tolerance), and the
execution path under ``REPRO_NE_KERNELS=ref``; the Pallas kernels run in
interpret mode off-TPU, so CPU CI exercises both sides of every pairing.
The numpy twins unpack a device result on the host.

Bit-packing convention (shared with the Pallas kernels and the host-side
numpy helpers): partition ``p`` lives at bit ``p % 32`` (LSB-first) of
word ``p // 32`` — ``words`` has shape ``(N, ceil(P/32))`` uint32.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# bit-packed replica sets
# ---------------------------------------------------------------------------

def replica_words(num_partitions: int) -> int:
    """Words per vertex of the packed replica set: ``ceil(P / 32)``."""
    return (num_partitions + 31) // 32


def pack_bits_ref(bools):
    """(N, P) bool → (N, ceil(P/32)) uint32, LSB-first within each word."""
    n, p = bools.shape
    w = replica_words(p)
    bp = jnp.pad(bools, ((0, 0), (0, w * 32 - p)))
    bits = jnp.arange(32, dtype=jnp.uint32)
    return (bp.reshape(n, w, 32).astype(jnp.uint32)
            << bits[None, None, :]).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits_ref(words, num_partitions: int):
    """(N, W) uint32 → (N, P) bool — exact inverse of ``pack_bits_ref``."""
    n, w = words.shape
    bits = jnp.arange(32, dtype=jnp.uint32)
    b = (words[:, :, None] >> bits[None, None, :]) & jnp.uint32(1)
    return b.reshape(n, w * 32)[:, :num_partitions].astype(bool)


def or_words_ref(a, b):
    """Element-wise OR-merge of two packed replica maps."""
    return a | b


# host-side (numpy) twins, for the driver/epilogue paths that unpack a
# device result after transfer — same bit layout, pinned by tests
def pack_bits_np(bools: np.ndarray) -> np.ndarray:
    n, p = bools.shape
    w = replica_words(p)
    bp = np.zeros((n, w * 32), np.uint32)
    bp[:, :p] = bools
    return (bp.reshape(n, w, 32)
            << np.arange(32, dtype=np.uint32)[None, None, :]).sum(
        axis=-1, dtype=np.uint32)


def unpack_bits_np(words: np.ndarray, num_partitions: int) -> np.ndarray:
    n, w = words.shape
    bits = np.arange(32, dtype=np.uint32)
    b = (words[:, :, None] >> bits[None, None, :]) & np.uint32(1)
    return b.reshape(n, w * 32)[:, :num_partitions].astype(bool)
