"""The benchmark's graphs, made from the configuration with numpy alone.

A copy, not an import, of the program's generators, so that a change to
``src/repro`` cannot change what the benchmark feeds it:

* :func:`graph500` — the Graph500 Kronecker (R-MAT) edge sample in
  chunks with spawned PRNG streams, then canonicalized (self-loops and
  duplicates removed, ``u < v``, sorted by ``(u, v)``).  It equals
  ``repro.io.spill_canonical_rmat`` edge for edge (chipbench/tests).
* :func:`grid_device` — the 2D-hash edge-to-device map, which the
  reference needs to order the two-hop quota as the devices do.
"""
from __future__ import annotations

import numpy as np

GRAPH500 = (0.57, 0.19, 0.19, 0.05)
CHUNK = 1 << 20


def _rmat_bits(rng, count: int, scale: int, probs) -> tuple:
    a, b, c, _ = probs
    u = np.zeros(count, np.int32)
    v = np.zeros(count, np.int32)
    for _ in range(scale):
        r = rng.random(count)
        right = r >= a + c
        lower = ((r >= a) & (r < a + c)) | (r >= a + b + c)
        u = (u << 1) | lower
        v = (v << 1) | right
    return u, v


def canonical(edges: np.ndarray, n: int) -> np.ndarray:
    """Loop-free, deduplicated, ``u < v``, sorted by ``(u, v)``; int32."""
    u = np.minimum(edges[:, 0], edges[:, 1]).astype(np.int64)
    v = np.maximum(edges[:, 0], edges[:, 1]).astype(np.int64)
    keep = u != v
    key = np.unique(u[keep] * n + v[keep])
    out = np.empty((key.shape[0], 2), np.int32)
    out[:, 0] = key // n
    out[:, 1] = key % n
    return out


def graph500(scale: int, edge_factor: int, seed: int,
             probs=GRAPH500) -> np.ndarray:
    """Canonical Graph500 Kronecker graph on ``2**scale`` vertices."""
    n = 1 << scale
    m = n * edge_factor
    num_chunks = (m + CHUNK - 1) // CHUNK
    children = np.random.SeedSequence(seed).spawn(num_chunks + 1)
    perm = np.random.default_rng(children[0]).permutation(n).astype(np.int32)
    chunks = []
    for i in range(num_chunks):
        count = min(CHUNK, m - i * CHUNK)
        u, v = _rmat_bits(np.random.default_rng(children[i + 1]), count,
                          scale, probs)
        chunks.append(np.stack([perm[u], perm[v]], axis=1))
    return canonical(np.concatenate(chunks), n)


def _hash(x: np.ndarray, salt: int) -> np.ndarray:
    x = (np.asarray(x).astype(np.uint32)
         + np.uint32((0x9E3779B9 * salt) & 0xFFFFFFFF))
    x = (x ^ (x >> 16)) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def grid_shape(num_devices: int) -> tuple[int, int]:
    r = int(np.floor(np.sqrt(num_devices)))
    while num_devices % r:
        r -= 1
    return r, num_devices // r


def grid_device(edges: np.ndarray, num_devices: int) -> np.ndarray:
    """Device of each edge under the 2D hash over an r x c device grid."""
    r, c = grid_shape(num_devices)
    hu = _hash(edges[:, 0], 0) % np.uint32(r)
    hv = _hash(edges[:, 1], 1) % np.uint32(c)
    return hu.astype(np.int32) * c + hv.astype(np.int32)


def build(config: dict) -> tuple[np.ndarray, int]:
    """The canonical edge list ``(m, 2)`` and vertex count of a config.

    The graph is the configuration's alone, not the run's seed: the
    program's shard shapes follow the edge count exactly, so a graph drawn
    from each seed would recompile the round program in every run.  The
    run's seed is ``NEConfig.seed``, which draws the random restarts.
    """
    g = config["graph"]
    if g["family"] == "graph500":
        return (graph500(g["scale"], g["edge_factor"], g["kronecker_seed"],
                         tuple(g["initiator"])), 1 << g["scale"])
    raise ValueError(f"unknown graph family {g['family']!r}")


def write_edgefile(path, edges: np.ndarray, n: int):
    """Write a canonical EdgeFile with the program's own writer."""
    from repro.io.edgefile import FLAG_CANONICAL, EdgeFile, EdgeFileWriter

    with EdgeFileWriter(path, num_vertices=n, dtype=np.int32,
                        flags=FLAG_CANONICAL) as w:
        w.append(edges)
    return EdgeFile(path)
