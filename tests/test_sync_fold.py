"""SyncVertexAllocations folds only a batch's new edge slots.

``_apply_alloc`` sorts the batch's new slots to the front and scatters
them in fixed chunks of ``sync_chunk(C)``; the eight-scatter form below
visits every slot of the shard.  On random batches both give the same
replica map, D_rest, partition counts and new-edge count, for the bool
and the packed replica map, on 1 and 4 CPU devices (a subprocess, since
the device count is fixed when jax starts).
"""
import json
import os
import subprocess
import sys

import pytest

from repro.dist.partitioner_sm import sync_chunk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTS = ["0", "1", "K-1", "K", "K+1", "3K+5", "C"]

_FOLD_CHECK = r"""
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.graph import target_histogram
from repro.dist import compat
from repro.dist.partitioner_sm import AXIS, _apply_alloc, sync_chunk
from repro.kernels.ne_round import ops as ne_ops

N, P_NUM, C = 3000, 40, 1 << 14
K = sync_chunk(C)
COUNTS = {"0": 0, "1": 1, "K-1": K - 1, "K": K, "K+1": K + 1,
          "3K+5": 3 * K + 5, "C": C}


def eight_scatters(new, part, u_loc, v_loc, n, p_num, vparts, degree_rest,
                   edges_per_part, num_dev):
    # the sync as it was: four scatters of every slot, two per endpoint,
    # the slots that are not new sent to row n and dropped
    newi = new.astype(jnp.int32)
    add = jnp.where(new, part, 0)
    counts = jax.lax.psum(target_histogram(jnp.where(new, part, -1), p_num),
                          AXIS)
    drop_u = jnp.where(new, u_loc, n)
    drop_v = jnp.where(new, v_loc, n)
    vnew = jnp.zeros((n, p_num), bool)
    vnew = vnew.at[drop_u, add].set(True, mode="drop")
    vnew = vnew.at[drop_v, add].set(True, mode="drop")
    if vparts.dtype == jnp.uint32:
        delta = compat.or_all_reduce(ne_ops.pack_bits(vnew), AXIS, num_dev)
        vparts = ne_ops.or_words(vparts, delta)
    else:
        vparts = vparts | (jax.lax.psum(vnew.astype(jnp.int32), AXIS) > 0)
    dec = (jnp.zeros((n,), jnp.int32)
           .at[drop_u].add(newi, mode="drop")
           .at[drop_v].add(newi, mode="drop"))
    degree_rest = degree_rest - jax.lax.psum(dec, AXIS)
    return vparts, degree_rest, edges_per_part + counts, counts.sum()


@partial(jax.jit, static_argnums=(0, 1))
def sync(fold, mesh, new, part, u, v, vparts, degree_rest, edges_per_part):
    num_dev = mesh.shape[AXIS]

    def body(new, part, u, v, vparts, degree_rest, edges_per_part):
        return fold(new[0], part[0], u[0], v[0], N, P_NUM, vparts,
                    degree_rest, edges_per_part, num_dev)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS, None),) * 4 + (P(),) * 3,
        out_specs=(P(),) * 4, check_vma=False,
    )(new, part, u, v, vparts, degree_rest, edges_per_part)


out = {}
for d in (1, 4):
    mesh = compat.make_mesh((d,), (AXIS,), devices=jax.devices()[:d])
    for packed in (False, True):
        for name, count in COUNTS.items():
            rng = np.random.default_rng(1000 * d + 10 * packed + len(out))
            new = np.zeros((d, C), bool)
            for row in new:
                row[rng.choice(C, count, replace=False)] = True
            part = np.where(new, rng.integers(0, P_NUM, (d, C)), -1)
            u = rng.integers(0, N, (d, C)).astype(np.int32)
            v = rng.integers(0, N, (d, C)).astype(np.int32)
            vparts = jnp.asarray(rng.random((N, P_NUM)) < 0.05)
            if packed:
                vparts = ne_ops.pack_bits(vparts)
            args = (jnp.asarray(new), jnp.asarray(part, jnp.int32),
                    jnp.asarray(u), jnp.asarray(v), vparts,
                    jnp.asarray(rng.integers(0, 1 << 20, N), jnp.int32),
                    jnp.asarray(rng.integers(0, 1 << 10, P_NUM),
                                jnp.int32))
            got = sync(_apply_alloc, mesh, *args)
            want = sync(eight_scatters, mesh, *args)
            out[f"{d}-{'packed' if packed else 'bool'}-{name}"] = {
                field: bool(np.array_equal(np.asarray(g), np.asarray(w)))
                for field, g, w in zip(
                    ("vparts", "degree_rest", "edges_per_part", "count"),
                    got, want)}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def fold_results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _FOLD_CHECK], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = proc.stdout.strip().splitlines()[-1]
    assert line.startswith("RESULT "), proc.stdout[-2000:]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("replica_map", ["bool", "packed"])
@pytest.mark.parametrize("new_slots", COUNTS)
def test_fold_matches_eight_scatters(fold_results, devices, replica_map,
                                     new_slots):
    """The chunked fold of ``new_slots`` new slots per device (K =
    ``sync_chunk(2**14)`` = 4096) equals the eight scatters over all C
    slots, field for field."""
    got = fold_results[f"{devices}-{replica_map}-{new_slots}"]
    assert got == dict.fromkeys(
        ("vparts", "degree_rest", "edges_per_part", "count"), True)


@pytest.mark.parametrize("c,k", [
    (1, 1), (1000, 1000), (1 << 14, 1 << 12), (262144, 1 << 12),
    (910072, 1 << 14), (1 << 20, 1 << 14), (3981037, 1 << 16),
    (15701786, 1 << 16)])
def test_sync_chunk_follows_the_shard_length(c, k):
    """A power of two near C/64 within [4096, 65536], never above C:
    the chunks of the unit shapes, the compile checks' shards and the
    benchmark's three shards (s16, s20 on four chips, s20 on one)."""
    assert sync_chunk(c) == k
