"""Engine-based full-graph GNN training step (vertex-cut, NE-partitioned).

The naive pjit formulation of full-graph message passing gathers node
features through GSPMD (which replicates the node tensor — fatal for
ogb_products × equiformer).  This step instead runs the PowerGraph-style
engine from ``repro.apps.engine`` under ``shard_map``: device d owns
partition d's edges (mirror-local indices), every layer does

  master→mirror broadcast (all_to_all) → local edge compute →
  local mirror aggregation → mirror→master reduce (all_to_all) → apply.

Per-layer wire bytes = 2·Σ_p|V(E_p)|·F — replication factor × |V| × F:
the Distributed NE quality metric *is* the collective term of the roofline
(the paper's Table 5 effect, measurable in the dry-run HLO).

The same body runs (a) the dry-run with synthetic capacities derived from
an assumed RF, and (b) real partitions from ``build_sharded_graph`` in
tests/benchmarks — where it is verified to match the plain single-device
model bit-for-bit (same params).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.apps import engine as eng
from repro.models.common import mlp_apply

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EngineCaps:
    """Static per-device capacities (padded)."""
    n_dev: int
    n_vertices: int
    c_edges: int        # local undirected edges
    r_mirrors: int
    o_owned: int
    l_lane: int         # per-(src,dst) all_to_all lane
    feat: int
    n_classes: int
    sync_dtype: str = "float32"   # mirror↔master wire dtype (§Perf lever)


def synth_caps(shape: dict, n_dev: int, rf: float = 4.0,
               alpha: float = 1.1) -> EngineCaps:
    n, e = shape["n_nodes"], shape["n_edges"]
    o = int(np.ceil(n / n_dev))
    r = int(np.ceil(rf * n / n_dev))
    return EngineCaps(
        n_dev=n_dev, n_vertices=n,
        c_edges=int(np.ceil(alpha * e / n_dev)),
        r_mirrors=r, o_owned=o,
        l_lane=int(np.ceil(r / n_dev * 1.3)) + 1,
        feat=shape["d_feat"], n_classes=shape["n_classes"])


def caps_from_sharded_graph(sg: eng.ShardedGraph, d_feat: int,
                            n_classes: int) -> EngineCaps:
    c = sg.caps
    return EngineCaps(n_dev=sg.num_devices, n_vertices=sg.num_vertices,
                      c_edges=c["C"], r_mirrors=c["R"], o_owned=c["O"],
                      l_lane=c["L"], feat=d_feat, n_classes=n_classes)


def engine_array_specs(caps: EngineCaps, positions: bool):
    d = caps.n_dev
    sds = jax.ShapeDtypeStruct
    out = dict(
        edges_ml=sds((d, caps.c_edges, 2), jnp.int32),
        emask=sds((d, caps.c_edges), jnp.bool_),
        send_idx=sds((d, d, caps.l_lane), jnp.int32),
        send_mask=sds((d, d, caps.l_lane), jnp.bool_),
        recv_owned=sds((d, d, caps.l_lane), jnp.int32),
        owned_mask=sds((d, caps.o_owned), jnp.bool_),
        feats=sds((d, caps.o_owned, caps.feat), jnp.float32),
        labels=sds((d, caps.o_owned), jnp.int32),
        label_mask=sds((d, caps.o_owned), jnp.bool_),
        positions=sds((d, caps.o_owned, 3), jnp.float32),
    )
    if not positions:
        out.pop("positions")
    return out


def engine_arrays(sg: eng.ShardedGraph, feats: np.ndarray,
                  labels: np.ndarray, label_mask: np.ndarray,
                  positions: np.ndarray | None):
    """Real arrays from a built ShardedGraph (host-side)."""
    d = sg.num_devices
    o = sg.caps["O"]
    f_o = np.zeros((d, o, feats.shape[1]), np.float32)
    y_o = np.zeros((d, o), np.int32)
    m_o = np.zeros((d, o), bool)
    p_o = np.zeros((d, o, 3), np.float32)
    for dd in range(d):
        sel = sg.owned_mask[dd]
        ids = sg.owned_glob[dd][sel]
        f_o[dd, sel] = feats[ids]
        y_o[dd, sel] = labels[ids]
        m_o[dd, sel] = label_mask[ids]
        if positions is not None:
            p_o[dd, sel] = positions[ids]
    out = dict(edges_ml=sg.edges_ml, emask=sg.emask, send_idx=sg.send_idx,
               send_mask=sg.send_mask, recv_owned=sg.recv_owned,
               owned_mask=sg.owned_mask, feats=f_o, labels=y_o,
               label_mask=m_o)
    if positions is not None:
        out["positions"] = p_o
    return {k: jnp.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# per-model engine layers (same param pytrees as models/gnn/* init_params)
# ---------------------------------------------------------------------------

def _bcast(x_o, a, caps, axis):
    wire = jnp.dtype(caps.sync_dtype)
    out = eng.master_to_mirror(x_o.astype(wire), a["send_idx"],
                               a["send_mask"], a["recv_owned"],
                               caps.r_mirrors, axis=axis)
    return out.astype(x_o.dtype)


def _reduce(x_m, a, caps, axis, op="sum", identity=0.0):
    wire = jnp.dtype(caps.sync_dtype)
    out = eng.mirror_to_master(x_m.astype(wire), a["send_idx"],
                               a["send_mask"], a["recv_owned"],
                               caps.o_owned, op,
                               jnp.asarray(identity, wire), axis=axis)
    return out.astype(x_m.dtype)


def _degrees(a, caps, axis):
    ones = a["emask"].astype(jnp.float32)[:, None]
    d_m = eng.scatter_edges(ones, ones, a["edges_ml"], a["emask"],
                            caps.r_mirrors)
    return _reduce(d_m, a, caps, axis)          # (O, 1)


def gin_forward(params, a, caps, cfg, axis):
    h = a["feats"]
    for lp in params["layers"]:
        h_m = _bcast(h, a, caps, axis)
        src, dst = a["edges_ml"][:, 0], a["edges_ml"][:, 1]
        agg_m = eng.scatter_edges(h_m[src], h_m[dst], a["edges_ml"],
                                  a["emask"], caps.r_mirrors)
        agg = _reduce(agg_m, a, caps, axis)
        h = jax.nn.relu(mlp_apply(lp["mlp"], (1.0 + lp["eps"]) * h + agg,
                                  act=jax.nn.relu))
    return mlp_apply(params["head"], h)


def pna_forward(params, a, caps, cfg, axis):
    h = a["feats"]
    deg = _degrees(a, caps, axis)[:, 0]
    logd = jnp.log1p(deg)[:, None]
    scalers = (jnp.ones_like(logd), logd / cfg.avg_log_deg,
               cfg.avg_log_deg / jnp.maximum(logd, 1e-3))
    src_dst = (a["edges_ml"][:, 0], a["edges_ml"][:, 1])
    for lp in params["layers"]:
        h_m = _bcast(h, a, caps, axis)
        src, dst = src_dst
        msg_d = mlp_apply(lp["pre"],
                          jnp.concatenate([h_m[src], h_m[dst]], -1),
                          act=jax.nn.relu)            # msg src→dst
        msg_s = mlp_apply(lp["pre"],
                          jnp.concatenate([h_m[dst], h_m[src]], -1),
                          act=jax.nn.relu)            # msg dst→src
        cnt = jnp.maximum(deg, 1.0)[:, None]
        s_ = _reduce(eng.scatter_edges(msg_d, msg_s, a["edges_ml"],
                                       a["emask"], caps.r_mirrors),
                     a, caps, axis)
        sq = _reduce(eng.scatter_edges(msg_d ** 2, msg_s ** 2, a["edges_ml"],
                                       a["emask"], caps.r_mirrors),
                     a, caps, axis)
        mx = _reduce(eng.scatter_edges(msg_d, msg_s, a["edges_ml"],
                                       a["emask"], caps.r_mirrors,
                                       "max", -jnp.inf),
                     a, caps, axis, "max", -jnp.inf)
        mn = _reduce(eng.scatter_edges(msg_d, msg_s, a["edges_ml"],
                                       a["emask"], caps.r_mirrors,
                                       "min", jnp.inf),
                     a, caps, axis, "min", jnp.inf)
        mean = s_ / cnt
        mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
        mn = jnp.where(jnp.isfinite(mn), mn, 0.0)
        std = jnp.sqrt(jnp.maximum(sq / cnt - mean * mean, 0.0) + 1e-6)
        aggs = [mean, mx, mn, std]
        stacked = [x * s for x in aggs for s in scalers]
        h = jax.nn.relu(mlp_apply(
            lp["post"], jnp.concatenate(stacked + [h], -1),
            act=jax.nn.relu))
    return mlp_apply(params["head"], h)


def egnn_forward(params, a, caps, cfg, axis):
    h, x = a["feats"], a["positions"]
    deg = jnp.maximum(_degrees(a, caps, axis)[:, 0], 1.0)
    for lp in params["layers"]:
        hx_m = _bcast(jnp.concatenate([h, x], -1), a, caps, axis)
        h_m, x_m = hx_m[:, :-3], hx_m[:, -3:]
        src, dst = a["edges_ml"][:, 0], a["edges_ml"][:, 1]
        rel_d = x_m[dst] - x_m[src]          # message src→dst
        d2 = (rel_d * rel_d).sum(-1, keepdims=True)
        m_d = mlp_apply(lp["phi_e"],
                        jnp.concatenate([h_m[dst], h_m[src], d2], -1),
                        act=jax.nn.silu, final_act=jax.nn.silu)
        m_s = mlp_apply(lp["phi_e"],
                        jnp.concatenate([h_m[src], h_m[dst], d2], -1),
                        act=jax.nn.silu, final_act=jax.nn.silu)
        coef_d = mlp_apply(lp["phi_x"], m_d, act=jax.nn.silu)
        coef_s = mlp_apply(lp["phi_x"], m_s, act=jax.nn.silu)
        xupd = _reduce(eng.scatter_edges(rel_d * coef_d, -rel_d * coef_s,
                                         a["edges_ml"], a["emask"],
                                         caps.r_mirrors),
                       a, caps, axis)
        x = x + xupd / deg[:, None]
        magg = _reduce(eng.scatter_edges(m_d, m_s, a["edges_ml"],
                                         a["emask"], caps.r_mirrors),
                       a, caps, axis)
        h = mlp_apply(lp["phi_h"], jnp.concatenate([h, magg], -1),
                      act=jax.nn.silu)
    return mlp_apply(params["head"], h)


def eqv2_forward(params, a, caps, cfg, axis, edge_chunk: int = 16384):
    """EquiformerV2 over the engine: chunked local eSCN conv + exact
    distributed segment softmax (max-reduce, then sum-reduce)."""
    from repro.models.gnn.equiformer_v2 import (_eq_norm, _m_groups,
                                                _so2_conv)
    from repro.models.gnn.wigner import (apply_blocks,
                                         rotation_to_edge_frame,
                                         sh_offsets, wigner_d_blocks)

    k, c, hh = cfg.n_coeff, cfg.d_hidden, cfg.n_heads
    o, r = caps.o_owned, caps.r_mirrors
    f = jnp.zeros((o, k, c))
    f = f.at[:, 0, :].set(a["feats"] @ params["embed"])
    pos_m = _bcast(a["positions"], a, caps, axis)          # (R, 3)
    src_u, dst_u = a["edges_ml"][:, 0], a["edges_ml"][:, 1]
    # directed local edges (both directions of each undirected edge)
    src = jnp.concatenate([src_u, dst_u])
    dst = jnp.concatenate([dst_u, src_u])
    emask = jnp.concatenate([a["emask"], a["emask"]])
    e_dir = src.shape[0]
    nch = max(1, -(-e_dir // edge_chunk))
    pad = nch * edge_chunk - e_dir
    srcp = jnp.pad(src, (0, pad))
    dstp = jnp.pad(dst, (0, pad))
    emp = jnp.pad(emask, (0, pad))
    centers = jnp.linspace(0.0, cfg.rbf_cutoff, cfg.n_rbf)
    g0, _ = _m_groups(cfg.l_max, cfg.m_max)

    def edge_geom(s_, d_):
        rel = pos_m[d_] - pos_m[s_]
        dist = jnp.linalg.norm(rel, axis=-1, keepdims=True)
        r_hat = rel / jnp.maximum(dist, 1e-6)
        rot = rotation_to_edge_frame(r_hat)
        rbf = jnp.exp(-((dist - centers[None, :]) ** 2)
                      * (cfg.n_rbf / cfg.rbf_cutoff) ** 2 * 0.5)
        return rot, rbf

    # layers are identical in structure — scan over stacked params so the
    # (large: Wigner + SO(2)) layer body is compiled once, not ×n_layers
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])

    def layer_body(f, lp):
        fn = _eq_norm(f, lp["norm_scale"], cfg.l_max)
        fn_m = _bcast(fn.reshape(o, k * c), a, caps, axis).reshape(r, k, c)

        def score_chunk(carry, idx):
            smax = carry
            s_, d_, m_ = srcp[idx], dstp[idx], emp[idx]
            rot, rbf = edge_geom(s_, d_)
            blocks = wigner_d_blocks(rot, cfg.l_max)
            f_rot = apply_blocks(blocks, fn_m[s_])
            msg = _so2_conv(lp, f_rot, rbf, cfg)
            sc = jax.nn.leaky_relu(msg[:, g0[0], :] @ lp["score"], 0.2)
            sc = jnp.where(m_[:, None], sc, -jnp.inf)
            smax = smax.at[d_].max(sc)
            return smax, None

        idxs = jnp.arange(nch * edge_chunk).reshape(nch, edge_chunk)
        init_smax = jax.lax.pcast(jnp.full((r, hh), -jnp.inf), axis,
                                  to="varying")
        smax_m, _ = jax.lax.scan(score_chunk, init_smax, idxs)
        smax_o = _reduce(smax_m, a, caps, axis, "max", -jnp.inf)
        smax_o = jnp.where(jnp.isfinite(smax_o), smax_o, 0.0)
        smax_back = _bcast(smax_o, a, caps, axis)           # (R, H)

        def msg_chunk(carry, idx):
            acc, wsum = carry
            s_, d_, m_ = srcp[idx], dstp[idx], emp[idx]
            rot, rbf = edge_geom(s_, d_)
            blocks = wigner_d_blocks(rot, cfg.l_max)
            f_rot = apply_blocks(blocks, fn_m[s_])
            msg = _so2_conv(lp, f_rot, rbf, cfg)
            sc = jax.nn.leaky_relu(msg[:, g0[0], :] @ lp["score"], 0.2)
            w = jnp.exp(sc - smax_back[d_])
            w = jnp.where(m_[:, None], w, 0.0)
            back = apply_blocks(blocks, msg, transpose=True)
            wh = back.reshape(-1, k, hh, c // hh) * w[:, None, :, None]
            acc = acc.at[d_].add(wh.reshape(-1, k * c))
            wsum = wsum.at[d_].add(w)
            return (acc, wsum), None

        init_acc = jax.tree.map(
            lambda x: jax.lax.pcast(x, axis, to="varying"),
            (jnp.zeros((r, k * c)), jnp.zeros((r, hh))))
        (acc_m, wsum_m), _ = jax.lax.scan(msg_chunk, init_acc, idxs)
        agg = _reduce(acc_m, a, caps, axis).reshape(o, k, hh, c // hh)
        wsum = _reduce(wsum_m, a, caps, axis)               # (O, H)
        agg = (agg / jnp.maximum(wsum[:, None, :, None], 1e-16)
               ).reshape(o, k, c)
        f = f + jnp.einsum("nkc,cd->nkd", agg, lp["wout"])
        # gated FFN (pointwise — masters only, identical to plain model)
        fn2 = _eq_norm(f, lp["norm_scale"], cfg.l_max)
        s0 = fn2[:, 0, :]
        upd0 = mlp_apply(lp["ffn0"], s0, act=jax.nn.silu)
        gates = jax.nn.sigmoid(jnp.einsum("nc,cld->nld", s0, lp["gate"]))
        outs = [upd0[:, None, :]]
        for l, (s_, d_) in enumerate(sh_offsets(cfg.l_max)):
            if l == 0:
                continue
            outs.append(fn2[:, s_:s_ + d_, :] * gates[:, None, l - 1, :])
        f = f + jnp.concatenate(outs, axis=-2)
        return f, None

    # remat: without it the two inner chunk-scans' carries are saved for
    # every layer (≈56 GB/layer at ogb_products scale) — recompute instead
    f, _ = jax.lax.scan(
        jax.checkpoint(layer_body,
                       policy=jax.checkpoint_policies.nothing_saveable),
        f, stacked)                               # f already device-varying
    return mlp_apply(params["head"], f[:, 0, :], act=jax.nn.silu)


ENGINE_FWD = {"gin": gin_forward, "pna": pna_forward, "egnn": egnn_forward,
              "equiformer_v2": eqv2_forward}


def make_engine_loss(model_module: str, cfg, caps: EngineCaps, mesh,
                     dev_axes: tuple[str, ...], has_positions: bool):
    """shard_map'd masked-CE loss over the engine forward.

    mesh=None → single "device" closure (no collectives needed: D=1 engine
    arrays still flow through all_to_all over a 1-mesh in tests).
    """
    fwd = ENGINE_FWD[model_module]

    def body(params, a):
        a = {k: v[0] for k, v in a.items()}   # strip the device dim
        logits = fwd(params, a, caps, cfg, dev_axes)
        lm = a["label_mask"]
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(a["labels"], logits.shape[-1])
        nll = logz - (logits * onehot).sum(-1)
        loss_sum = jnp.where(lm, nll, 0.0).sum()
        cnt = lm.sum()
        loss = jax.lax.psum(loss_sum, dev_axes) \
            / jnp.maximum(jax.lax.psum(cnt, dev_axes), 1)
        return loss

    if mesh is None:
        raise ValueError("engine loss needs a mesh (use make_host_mesh)")

    aspec = P(dev_axes)

    def loss_fn(params, arrays):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: aspec, arrays)),
            out_specs=P(),
        )(params, arrays)

    return loss_fn
