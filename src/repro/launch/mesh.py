"""Production mesh builders.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  Target: TPU v5e pods — 256 chips per pod
(16×16), two pods = 512 chips for the multi-pod dry-run.
"""
from __future__ import annotations

import jax

from repro.dist import compat


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever host devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return compat.make_mesh((n // model, model), ("data", "model"))

