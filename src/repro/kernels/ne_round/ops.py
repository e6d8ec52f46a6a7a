"""Front door for the ne_round packing kernels.

Dispatch contract (mirrors ``block_spmm``/``flash_attention``, plus an
impl override):

* ``NEConfig.use_pallas`` decides whether the SPMD round keeps its
  replica sets bit-packed, through ``pack_bits``/``unpack_bits``/
  ``or_words`` below.  A ``None`` field resolves from
  ``REPRO_NE_KERNELS`` at config construction (``env_enabled``), so the
  resolved config is self-contained and its fingerprint stable.
* ``REPRO_NE_KERNELS=ref`` keeps the packed representation but routes the
  ops to the XLA reference implementation — same bits, no Pallas import.
  The CI A/B lever and the escape hatch for backends without Pallas.
* Otherwise the ops run the Pallas kernels, in interpret mode off-TPU.

The rest of the round (one-hop claims, boundary top-k, claim scatter-min)
is XLA in ``repro.core.partitioner``: it needs a 1-D gather over an (N,)
table, ``top_k`` and scatter-min, none of which Pallas TPU lowers.

The Pallas module is imported lazily, only when a call actually
dispatches to it — importing this module (and therefore
``repro.core.partitioner``) never pulls Pallas TPU lowering.  CI guards
this (tests/test_kernels.py + the lint grep).
"""
from __future__ import annotations

import os

from repro.kernels.ne_round import ref
from repro.kernels.ne_round.ref import (  # noqa: F401  (re-exports)
    pack_bits_np,
    replica_words,
    unpack_bits_np,
)

ENV_VAR = "REPRO_NE_KERNELS"


def env_enabled() -> bool:
    """Default for ``NEConfig.use_pallas`` when left as ``None``."""
    v = os.environ.get(ENV_VAR, "").strip().lower()
    return v not in ("", "0", "off", "false", "no")


def use_ref_impl() -> bool:
    """``REPRO_NE_KERNELS=ref`` → run the family as pure XLA."""
    return os.environ.get(ENV_VAR, "").strip().lower() == "ref"


def _pallas():
    # lazy: keeps repro.core / repro.io free of Pallas imports
    from repro.kernels.ne_round import ne_round
    return ne_round


def _interpret() -> bool:
    import jax
    return jax.default_backend() != "tpu"


def pack_bits(bools):
    if use_ref_impl():
        return ref.pack_bits_ref(bools)
    return _pallas().pack_bits(bools, interpret=_interpret())


def unpack_bits(words, num_partitions: int):
    if use_ref_impl():
        return ref.unpack_bits_ref(words, num_partitions)
    return _pallas().unpack_bits(words, num_partitions,
                                 interpret=_interpret())


def or_words(a, b):
    if use_ref_impl():
        return ref.or_words_ref(a, b)
    return _pallas().or_words(a, b, interpret=_interpret())
