"""SPMD and multi-process helpers that ``jax`` does not spell directly.

``or_all_reduce`` (a bitwise-OR all-reduce that keeps packed words
packed), ``make_mesh`` (Auto axis types, which ``jax.make_mesh`` no longer
defaults to), and the process-level wrappers — ``process_env``,
``barrier`` and the ``all_processes_*`` reductions — that behave the same
whether or not ``jax.distributed`` was initialized.
"""
from __future__ import annotations

import jax

__all__ = ["all_processes_any", "all_processes_min", "all_processes_sum",
           "barrier", "make_mesh", "or_all_reduce", "process_env"]


def or_all_reduce(x, axis_name, num_devices: int):
    """Bitwise-OR all-reduce of an integer array over a shard_map axis.

    jax.lax has no ``por``; the usual spelling ``psum(x != 0) > 0`` would
    re-widen the packed uint32 replica words back to one int32 *per bit*.
    This keeps the payload packed: recursive doubling over ``ppermute``
    (log2 D steps, each moving only the packed words) when the axis size
    is a power of two, else one ``all_gather`` + fold.  Both are exact
    bitwise OR, so results are bit-identical either way.

    ``num_devices`` is the static axis size (from the mesh shape).
    """
    d = int(num_devices)
    if d <= 1:
        return x
    if d & (d - 1) == 0:
        step = 1
        while step < d:
            x = x | jax.lax.ppermute(
                x, axis_name, [(i, i ^ step) for i in range(d)])
            step *= 2
        return x
    gathered = jax.lax.all_gather(x, axis_name)
    out = gathered[0]
    for i in range(1, d):
        out = out | gathered[i]
    return out


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types (it defaults to Explicit)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


def process_env() -> tuple[int, int]:
    """(process index, process count) — (0, 1) outside ``jax.distributed``.

    Failure-tolerant so call sites behave identically whether or not the
    distributed runtime was ever initialized (single-controller runs, unit
    tests, jax-free spawn workers that import this lazily).
    """
    try:
        return int(jax.process_index()), int(jax.process_count())
    except Exception:
        return 0, 1


def barrier(name: str) -> None:
    """Cross-process sync point; a no-op in single-process runs.

    Realized as ``multihost_utils.sync_global_devices`` — a psum over every
    global device — so it doubles as a liveness check: if a peer process
    died, the collective fails instead of silently proceeding on a torn
    cluster.  ``name`` must be passed identically (and in the same order)
    by every process.
    """
    if process_env()[1] == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def all_processes_min(value: int) -> int:
    """Minimum of a host-side int across all processes (identity locally).

    Used by barrier'd resume to agree on the newest snapshot round that
    *every* host can fully load — the 'last fully-published round wins'
    half of the snapshot protocol.
    """
    if process_env()[1] == 1:
        return int(value)
    import numpy as np
    from jax.experimental import multihost_utils

    vals = multihost_utils.process_allgather(np.int64(value))
    return int(np.min(vals))


def all_processes_sum(value: int) -> int:
    """Sum of a host-side int across all processes (identity locally).

    The sharded finalize uses it to agree on the *global* leftover count
    from per-host partials — the scalar half of the metrics-combine step.
    """
    if process_env()[1] == 1:
        return int(value)
    import numpy as np
    from jax.experimental import multihost_utils

    vals = multihost_utils.process_allgather(np.int64(value))
    return int(np.sum(vals))


# per-host scratch budget for the chunked allgather-OR below: each chunk
# materializes H copies of `chunk` bools, so chunk = BUDGET / H keeps the
# peak flat as the host count grows
_ANY_CHUNK_BYTES = 64 << 20


def all_processes_any(mask):
    """Element-wise OR of a host-side bool array across all processes
    (identity locally).

    The array half of the sharded finalize's metrics-combine: each host
    applies only its own slices' leftover updates to its replica-map
    copy, and the per-host deltas merge into the global ``V(E_p)`` here —
    O(N·P) communication, never O(M).  The allgather runs in fixed-byte
    chunks (every process iterates the same boundaries, so it stays a
    valid collective sequence): a whole-array ``process_allgather`` would
    stage H copies of the replica map on every host, re-growing the
    per-host envelope with the cluster size the sharded epilogue exists
    to cap.
    """
    import numpy as np

    mask = np.asarray(mask, bool)
    nprocs = process_env()[1]
    if nprocs == 1:
        return mask
    from jax.experimental import multihost_utils

    flat = mask.reshape(-1)
    out = np.empty_like(flat)
    chunk = max(1, _ANY_CHUNK_BYTES // nprocs)
    for i in range(0, flat.size, chunk):
        gathered = multihost_utils.process_allgather(flat[i:i + chunk])
        out[i:i + chunk] = np.any(gathered, axis=0)
    return out.reshape(mask.shape)
