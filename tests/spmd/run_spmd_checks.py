"""SPMD integration checks, run in a subprocess with 8 host devices.

Prints one JSON line with all results; the pytest wrapper asserts on it.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import json  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import NEConfig, evaluate, partition  # noqa: E402
from repro.dist import compat  # noqa: E402
from repro.dist.partitioner_sm import partition_spmd  # noqa: E402
from repro.apps.engine import build_sharded_graph  # noqa: E402
from repro.apps.algorithms import pagerank, sssp, wcc  # noqa: E402
from repro.graphs.generators import barabasi_albert  # noqa: E402
from repro.core.graph import to_networkx  # noqa: E402

out = {"devices": len(jax.devices())}

g = barabasi_albert(400, 3, seed=2)
e = np.asarray(g.edges)
cfg = NEConfig(num_partitions=8, seed=0, k_sel=64, edge_chunk=1 << 12)

# --- distributed partitioner vs single-controller --------------------------
res_sc = partition(g, cfg)
res_sm = partition_spmd(g, cfg)
st_sc = evaluate(e, res_sc.edge_part, g.num_vertices, 8)
st_sm = evaluate(e, res_sm.edge_part, g.num_vertices, 8)
out["rf_single"] = st_sc.replication_factor
out["rf_spmd"] = st_sm.replication_factor
out["eb_spmd"] = st_sm.edge_balance
out["spmd_all_assigned"] = bool((res_sm.edge_part >= 0).all())

# --- fused ne_round kernels + bit-packed replica sets: bit-identity --------
import dataclasses  # noqa: E402

cfg_pl = dataclasses.replace(cfg, use_pallas=True)
res_pl = partition_spmd(g, cfg_pl)
out["pallas_spmd_identical"] = bool(
    (res_pl.edge_part == res_sm.edge_part).all()
    and (res_pl.vparts == res_sm.vparts).all()
    and (res_pl.edges_per_part == res_sm.edges_per_part).all())
res_pl_sc = partition(g, dataclasses.replace(cfg, use_pallas=True))
out["pallas_single_identical"] = bool(
    (res_pl_sc.edge_part == res_sc.edge_part).all()
    and (res_pl_sc.vparts == res_sc.vparts).all())

# packed OR all-reduce == bool psum path, on the real 8-device mesh
from jax.sharding import PartitionSpec as PSpec  # noqa: E402
from repro.kernels.ne_round import ops as ne_ops  # noqa: E402

rng_or = np.random.default_rng(11)
bool_sh = rng_or.random((8, 128, 37)) < 0.1          # P=37: not 32-aligned
mesh_or = compat.make_mesh((8,), ("shard",))


def _or_body(b):
    words = ne_ops.pack_bits(b[0])
    red = compat.or_all_reduce(words, "shard", 8)
    return ne_ops.unpack_bits(red, 37)[None]


or_out = jax.shard_map(
    _or_body, mesh=mesh_or, in_specs=(PSpec("shard", None, None),),
    out_specs=PSpec("shard", None, None), check_vma=False,
)(jax.numpy.asarray(bool_sh))
out["pallas_or_reduce_ok"] = bool(
    (np.asarray(or_out) == bool_sh.any(axis=0)[None]).all())

# --- GAS engine apps vs networkx -------------------------------------------
sg = build_sharded_graph(e, res_sm.edge_part, g.num_vertices, 8)
gx = to_networkx(g)

import networkx as nx  # noqa: E402

pr = pagerank(sg, iters=40)
pr_nx = nx.pagerank(gx, alpha=0.85, max_iter=200, tol=1e-10)
pr_ref = np.array([pr_nx[i] for i in range(g.num_vertices)])
out["pr_max_err"] = float(np.abs(pr - pr_ref).max())

dist, it_s = sssp(sg, source=0)
d_nx = nx.single_source_shortest_path_length(gx, 0)
d_ref = np.full(g.num_vertices, np.inf)
for k, v in d_nx.items():
    d_ref[k] = v
finite = np.isfinite(d_ref)
out["sssp_match"] = bool((dist[finite] == d_ref[finite]).all())
out["sssp_iters"] = it_s

labels, it_w = wcc(sg)
comp_ref = {}
for i, comp in enumerate(nx.connected_components(gx)):
    m = min(comp)
    for v in comp:
        comp_ref[v] = m
lab_ref = np.array([comp_ref.get(i, -1) for i in range(g.num_vertices)])
has_edge = lab_ref >= 0
out["wcc_match"] = bool((labels[has_edge] == lab_ref[has_edge]).all())

# --- engine GNN forward == plain single-device forward ----------------------
import jax.numpy as jnp  # noqa: E402

from repro.launch import gnn_engine as ge  # noqa: E402
from repro.models.gnn import gin as gin_mod  # noqa: E402
from repro.models.gnn import egnn as egnn_mod  # noqa: E402
from repro.models.gnn import equiformer_v2 as eq_mod  # noqa: E402
from repro.models.gnn.common import GraphData, to_directed_padded  # noqa: E402

mesh = compat.make_mesh((8,), ("data",))
gsm = barabasi_albert(300, 3, seed=5)
esm = np.asarray(gsm.edges)
nsm = gsm.num_vertices
rng = np.random.default_rng(1)
feats = rng.normal(size=(nsm, 10)).astype(np.float32)
pos = rng.normal(size=(nsm, 3)).astype(np.float32)
labels = rng.integers(0, 3, nsm).astype(np.int32)
res_g = partition(gsm, NEConfig(num_partitions=8, seed=1, k_sel=32,
                                edge_chunk=1 << 12))
sg2 = build_sharded_graph(esm, res_g.edge_part, nsm, 8)

from repro.models.gnn import pna as pna_mod  # noqa: E402

for mod_name, mod, cfg in [
    ("gin", gin_mod, gin_mod.GINConfig(n_layers=2, d_hidden=16, d_feat=10,
                                       n_classes=3)),
    ("pna", pna_mod, pna_mod.PNAConfig(n_layers=2, d_hidden=16, d_feat=10,
                                       n_classes=3)),
    ("egnn", egnn_mod, egnn_mod.EGNNConfig(n_layers=2, d_hidden=16,
                                           d_feat=10, n_classes=3)),
    ("equiformer_v2", eq_mod, eq_mod.EquiformerV2Config(
        n_layers=1, d_hidden=8, l_max=2, m_max=2, n_heads=2, d_feat=10,
        n_classes=3)),
]:
    params = mod.init_params(jax.random.PRNGKey(2), cfg)
    caps = ge.caps_from_sharded_graph(sg2, 10, 3)
    arrays = ge.engine_arrays(sg2, feats, labels, np.ones(nsm, bool), pos)
    loss_eng = ge.make_engine_loss(mod_name, cfg, caps, mesh, ("data",),
                                   has_positions=True)(params, arrays)
    # plain single-device reference
    ei, em = to_directed_padded(esm, nsm)
    gref = GraphData(jnp.asarray(feats), jnp.asarray(ei), jnp.asarray(em),
                     positions=jnp.asarray(pos))
    logits = mod.forward(params, gref, cfg).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    oh = jax.nn.one_hot(labels, 3)
    loss_ref = (logz - (logits * oh).sum(-1)).mean()
    err = abs(float(loss_eng) - float(loss_ref))
    out[f"engine_{mod_name}_loss_err"] = err

# --- split-KV decode: seq-sharded cache == unsharded decode -----------------
from jax.sharding import NamedSharding  # noqa: E402

from repro.dist.sharding import lm_rules  # noqa: E402
from repro.models.lm import transformer as tfm  # noqa: E402

mesh2 = compat.make_mesh((2, 4), ("data", "model"))
lcfg = tfm.LMConfig(name="dec", n_layers=2, d_model=32, n_heads=8,
                    n_kv_heads=2, d_ff=64, vocab=64, head_dim=8,
                    dtype=jnp.float32, remat="none")
lp = tfm.init_params(jax.random.PRNGKey(3), lcfg)
smax = 32
kc = jax.random.normal(jax.random.PRNGKey(4),
                       (lcfg.n_layers, 1, smax, 2, 8)) * 0.3
vc = jax.random.normal(jax.random.PRNGKey(5),
                       (lcfg.n_layers, 1, smax, 2, 8)) * 0.3
tok = jnp.array([[7]], jnp.int32)
clen = jnp.int32(smax - 1)
ref_logits, _, _ = tfm.decode(lp, tok, (kc, vc), clen, lcfg)
# sharded: kv heads can't shard (2 < 4) → cache seq over both axes
rules = lm_rules(batch_axes=(), tp="model", q_ok=True, kv_ok=False,
                 seq_kv_axes=("data", "model"))
cache_sh = NamedSharding(mesh2, rules["kv_cache"])
with jax.sharding.set_mesh(mesh2):
    kc_s = jax.device_put(kc, cache_sh)
    vc_s = jax.device_put(vc, cache_sh)
    sh_logits, _, _ = jax.jit(
        lambda p, t, k, v, c: tfm.decode(p, t, (k, v), c, lcfg, rules)
    )(lp, tok, kc_s, vc_s, clen)
out["splitkv_decode_err"] = float(jnp.abs(sh_logits - ref_logits).max())

# --- MoE: explicit-EP shard_map path == dense dispatch path -----------------
from repro.dist.context import mesh_context  # noqa: E402
from repro.models.lm.moe import MoEConfig, init_moe, moe_block  # noqa: E402

# capacity_factor high enough that neither path drops tokens — dropping
# granularity (global vs per-shard positions) is the one designed divergence
mcfg = MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=4.0)
mp = init_moe(jax.random.PRNGKey(6), 24, mcfg, jnp.float32)
xm = jax.random.normal(jax.random.PRNGKey(7), (4, 6, 24))
y_dense, aux_dense = moe_block(mp, xm, mcfg, None)
with mesh_context(mesh2, batch_axes=("data",), model_axis="model"), \
        jax.sharding.set_mesh(mesh2):
    y_ep, aux_ep = jax.jit(lambda p, x: moe_block(p, x, mcfg, None))(mp, xm)
out["moe_ep_err"] = float(jnp.abs(y_dense - y_ep).max())
out["moe_aux_err"] = float(jnp.abs(aux_dense - aux_ep))

# --- all_to_all edge redistribution: partition p's edges land on device p ---
from repro.core.graph import shard_edges  # noqa: E402
from repro.dist.redistribute import redistribute_edges  # noqa: E402

shards_r, masks_r, _, dev_r = shard_edges(e, 8, salt=0)
parts_r = np.zeros(masks_r.shape, np.int32)
for dd in range(8):
    sel = np.nonzero(dev_r == dd)[0]
    parts_r[dd, : sel.size] = res_sm.edge_part[sel]
edges_out, mask_out, dropped = redistribute_edges(shards_r, masks_r,
                                                  parts_r)
ok_redis = dropped == 0
for dd in range(8):
    got = edges_out[dd][mask_out[dd]]
    want = e[res_sm.edge_part == dd]
    key_got = np.sort(got[:, 0].astype(np.int64) * 100000 + got[:, 1])
    key_want = np.sort(want[:, 0].astype(np.int64) * 100000 + want[:, 1])
    ok_redis &= key_got.tolist() == key_want.tolist()
out["redistribute_ok"] = bool(ok_redis)

# --- runtime driver: kill-at-round-k resume bit-identity on 8 devices -------
import tempfile  # noqa: E402

from repro.runtime import PartitionDriver, load_artifact  # noqa: E402

ne_cfg = NEConfig(num_partitions=8, seed=0, k_sel=64, edge_chunk=1 << 12)
with tempfile.TemporaryDirectory() as td:
    snap_dir = td + "/snap"
    drv = PartitionDriver(g, ne_cfg, snapshot_dir=snap_dir, snapshot_every=1,
                          keep=100_000)
    res_drv = drv.run()
    out["driver_matches_spmd"] = bool(
        (res_drv.edge_part == res_sm.edge_part).all()
        and (res_drv.vparts == res_sm.vparts).all()
        and res_drv.rounds == res_sm.rounds)
    k = max(res_drv.rounds // 2, 1)
    drv2 = PartitionDriver.resume(g, ne_cfg, snap_dir, round_k=k)
    res_back = drv2.run()
    out["driver_resume_identical"] = bool(
        (res_back.edge_part == res_drv.edge_part).all()
        and (res_back.vparts == res_drv.vparts).all())
    art = drv.save_artifact(td + "/art")
    loaded = load_artifact(td + "/art")
    out["artifact_roundtrip"] = bool(
        (loaded.edge_part == res_drv.edge_part).all()
        and (loaded.vparts == res_drv.vparts).all()
        and (loaded.edges == e).all())

print("RESULT " + json.dumps(out))
