"""Distributed graph applications over edge partitions (paper §7.6, Table 5).

PageRank / SSSP / WCC on the vertex-cut GAS engine.  Each runs as a single
jitted ``shard_map`` program; per-superstep traffic is the mirror↔master
all_to_all pair, so partition quality (replication factor) directly sets
the wire bytes — exactly the effect the paper measures on PowerLyra.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.apps.engine import (AXIS, ShardedGraph, master_to_mirror,
                               mirror_to_master, scatter_edges)
from repro.dist import compat

INF = jnp.float32(jnp.inf)


def _specs(n_args):
    return tuple(P(AXIS) for _ in range(n_args))


def _unpack(sg: ShardedGraph):
    return (jnp.asarray(sg.edges_ml), jnp.asarray(sg.emask),
            jnp.asarray(sg.send_idx), jnp.asarray(sg.send_mask),
            jnp.asarray(sg.recv_owned), jnp.asarray(sg.owned_mask))


def _mesh(sg: ShardedGraph, mesh):
    if mesh is None:
        mesh = compat.make_mesh((sg.num_devices,), (AXIS,))
    assert mesh.shape[AXIS] == sg.num_devices
    return mesh


def _stitch(sg: ShardedGraph, out_padded: np.ndarray, fill: float):
    """(D, O) padded master values → (N,) host array."""
    res = np.full((sg.num_vertices,), fill, np.float64)
    for d in range(sg.num_devices):
        mask = sg.owned_mask[d]
        res[sg.owned_glob[d][mask]] = out_padded[d][mask]
    return res


def pagerank(sg: ShardedGraph, mesh=None, iters: int = 30,
             damping: float = 0.85) -> np.ndarray:
    mesh = _mesh(sg, mesh)
    n = sg.num_vertices
    caps = sg.caps

    def body(edges_ml, emask, send_idx, send_mask, recv_owned, owned_mask):
        edges_ml, emask = edges_ml[0], emask[0]
        send_idx, send_mask = send_idx[0], send_mask[0]
        recv_owned, owned_mask = recv_owned[0], owned_mask[0]
        src, dst = edges_ml[:, 0], edges_ml[:, 1]
        ones = emask.astype(jnp.float32)[:, None]
        deg_m = scatter_edges(ones, ones, edges_ml, emask, caps["R"])
        deg_o = mirror_to_master(deg_m, send_idx, send_mask, recv_owned,
                                 caps["O"])
        pr = jnp.where(owned_mask[:, None], 1.0 / n, 0.0)

        def step(_, pr):
            contrib = jnp.where(deg_o > 0, pr / jnp.maximum(deg_o, 1.0), 0.0)
            c_m = master_to_mirror(contrib, send_idx, send_mask, recv_owned,
                                   caps["R"])
            ev_dst = c_m[src] * emask[:, None]
            ev_src = c_m[dst] * emask[:, None]
            acc = scatter_edges(ev_dst, ev_src, edges_ml, emask, caps["R"])
            s = mirror_to_master(acc, send_idx, send_mask, recv_owned,
                                 caps["O"])
            return jnp.where(owned_mask[:, None],
                             (1.0 - damping) / n + damping * s, 0.0)

        return jax.lax.fori_loop(0, iters, step, pr)[None]

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=_specs(6),
                                  out_specs=P(AXIS)))
    out = np.asarray(fn(*_unpack(sg)))[:, :, 0]
    return _stitch(sg, out, fill=(1.0 - damping) / n)


def _label_propagation(sg: ShardedGraph, mesh, init_fn, relax_add: float,
                       max_iters: int):
    """Shared min-propagation driver for SSSP (+1 relax) and WCC (+0)."""
    mesh = _mesh(sg, mesh)
    caps = sg.caps

    def body(edges_ml, emask, send_idx, send_mask, recv_owned, owned_mask,
             init_vals):
        edges_ml, emask = edges_ml[0], emask[0]
        send_idx, send_mask = send_idx[0], send_mask[0]
        recv_owned, owned_mask = recv_owned[0], owned_mask[0]
        init_vals = init_vals[0]
        src, dst = edges_ml[:, 0], edges_ml[:, 1]
        val = jnp.where(owned_mask[:, None], init_vals, INF)

        def cond(carry):
            val, changed, it = carry
            return changed & (it < max_iters)

        def step(carry):
            val, _, it = carry
            v_m = master_to_mirror(val, send_idx, send_mask, recv_owned,
                                   caps["R"])
            ev_dst = jnp.where(emask[:, None], v_m[src] + relax_add, INF)
            ev_src = jnp.where(emask[:, None], v_m[dst] + relax_add, INF)
            acc = scatter_edges(ev_dst, ev_src, edges_ml, emask, caps["R"],
                                op="min", identity=INF)
            upd = mirror_to_master(acc, send_idx, send_mask, recv_owned,
                                   caps["O"], op="min", identity=INF)
            new = jnp.minimum(val, upd)
            changed = jax.lax.psum(
                (new < val).any().astype(jnp.int32), AXIS) > 0
            return new, changed, it + 1

        out, _, iters = jax.lax.while_loop(
            cond, step, (val, jnp.bool_(True), jnp.int32(0)))
        return out[None], iters[None]

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=_specs(7),
                                  out_specs=(P(AXIS), P(AXIS))))
    init_vals = init_fn()
    out, iters = fn(*_unpack(sg), jnp.asarray(init_vals))
    return np.asarray(out)[:, :, 0], int(np.asarray(iters)[0])


def sssp(sg: ShardedGraph, source: int, mesh=None, max_iters: int = 200):
    def init():
        vals = np.full((sg.num_devices, sg.caps["O"], 1), np.inf, np.float32)
        for d in range(sg.num_devices):
            hit = np.nonzero((sg.owned_glob[d] == source)
                             & sg.owned_mask[d])[0]
            vals[d, hit] = 0.0
        return vals

    out, iters = _label_propagation(sg, mesh, init, 1.0, max_iters)
    return _stitch(sg, out, fill=np.inf), iters


def wcc(sg: ShardedGraph, mesh=None, max_iters: int = 200):
    def init():
        return sg.owned_glob.astype(np.float32)[:, :, None]

    out, iters = _label_propagation(sg, mesh, init, 0.0, max_iters)
    return _stitch(sg, out, fill=-1.0), iters
