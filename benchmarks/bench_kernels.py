"""Kernel micro-benchmarks (interpret-mode timings are *structural* only;
the derived column reports the roofline-relevant operation counts) and the
partition-locality effect: Distributed NE lowers the nonzero-block count
of the block-CSR adjacency vs random order — fewer MXU block matmuls.

The ne_round rows time the edge-list one-hop of the expansion round (one
pass over M edges; XLA on every backend) against the CSR-slot chain,
asserting bit-identity in-line, and account the
SyncVertexAllocations collective payload: bit-packed replica words must
move ≥8× fewer bytes than the (N, P) int32 psum of the bool path (the
smoke-gate assertion).  Off-TPU the Pallas packing kernels run in
interpret mode, so the us ratios here measure structure, not silicon —
the payload byte accounting is exact everywhere.
"""
import numpy as np

from benchmarks.common import record, timeit
from repro.core import NEConfig, partition
from repro.graphs.rmat import rmat
from repro.kernels.block_spmm.block_spmm import build_block_csr

NE_SCALE = 16          # the ISSUE-6 reference scale for the ne_round rows
NE_PARTS = 16


def _locality_row():
    g = rmat(12, 8, seed=13)
    e = np.asarray(g.edges)
    n = g.num_vertices
    # nnz blocks with node ids in arrival order
    _, blocks_rand, _ = build_block_csr(e, n, 128, 128)
    nb_rand = int((np.abs(blocks_rand).sum((2, 3)) > 0).sum())
    # relabel nodes by NE partition → locality clusters the blocks
    res = partition(g, NEConfig(num_partitions=16, seed=0))
    owner = np.full(n, 16, np.int32)
    # primary owner = partition of first incident edge
    for (u, v), pp in zip(e, res.edge_part):
        owner[u] = min(owner[u], pp)
        owner[v] = min(owner[v], pp)
    order = np.argsort(owner, kind="stable")
    relabel = np.empty(n, np.int64)
    relabel[order] = np.arange(n)
    e2 = relabel[e]
    _, blocks_ne, _ = build_block_csr(e2, n, 128, 128)
    nb_ne = int((np.abs(blocks_ne).sum((2, 3)) > 0).sum())
    record("kernel_blockcsr_locality", 0.0,
           f"nnz_blocks_random_order={nb_rand};ne_order={nb_ne};"
           f"reduction={1 - nb_ne / nb_rand:.1%}")


def _ne_rows(scale: int, repeats: int):
    import jax
    import jax.numpy as jnp

    from repro.core.graph import as_graph
    from repro.core.partitioner import (I32_INF, alpha_limit, one_hop,
                                        vertex_claims)
    from repro.dist import compat
    from repro.kernels.ne_round import ops as ne_ops
    from repro.runtime import PartitionDriver

    g = as_graph(rmat(scale, 8, seed=13))
    n, m, p_num = g.num_vertices, g.num_edges, NE_PARTS
    cfg = NEConfig(num_partitions=p_num, seed=0, use_pallas=False).clamped(n)
    limit = alpha_limit(cfg.alpha, m, p_num)
    # a mid-run state (3 XLA rounds in) so claim keys / boundaries are
    # realistically dense, not the degenerate round-0 shapes; one device's
    # shard holds every edge in edge order
    drv = PartitionDriver(g, cfg, num_devices=1)
    for _ in range(3):
        drv.step()
    state = drv.state._replace(edge_part=drv.state.edge_part[0])
    _, sub = jax.random.split(state.key)
    vclaim = vertex_claims(cfg, limit, state.vparts, state.degree_rest,
                           state.edges_per_part, sub)
    u, v = g.edges[:, 0], g.edges[:, 1]

    # --- ne_claims: edge-list one-hop vs the 5-pass CSR segment_min chain --
    @jax.jit
    def xla_chain(vc, ep):
        slot_key = vc[g.slot_src]
        slot_ok = (slot_key < I32_INF) & (ep[g.adj_eid] < 0)
        slot_key = jnp.where(slot_ok, slot_key, I32_INF)
        ekey = jax.ops.segment_min(slot_key, g.adj_eid, num_segments=m)
        new1 = ekey < I32_INF
        part1 = jnp.where(new1, ekey % p_num, -1)
        counts = jnp.zeros((p_num,), jnp.int32).at[
            jnp.where(new1, part1, 0)].add(new1.astype(jnp.int32))
        return part1, counts

    @jax.jit
    def edge_one_hop(vc, ep):
        return one_hop(vc, u, v, ep, p_num)

    px, cx = jax.block_until_ready(xla_chain(vclaim, state.edge_part))
    pp, cp = jax.block_until_ready(edge_one_hop(vclaim, state.edge_part))
    assert (np.asarray(px) == np.asarray(pp)).all()
    assert (np.asarray(cx) == np.asarray(cp)).all()
    t_x = timeit(lambda: jax.block_until_ready(
        xla_chain(vclaim, state.edge_part)), repeats=repeats)
    t_p = timeit(lambda: jax.block_until_ready(
        edge_one_hop(vclaim, state.edge_part)), repeats=repeats)
    record("ne_claims", t_p * 1e6,
           f"scale={scale};edges={m};xla_us={t_x * 1e6:.1f};"
           f"edgelist_over_chain={t_p / t_x:.2f}x;bit_identical=True")

    # --- ne_or_reduce: packed OR all-reduce vs bool int32 psum -------------
    # payload accounting is exact and platform-independent: the array each
    # device hands to the collective, per SyncVertexAllocations call
    w = ne_ops.replica_words(p_num)
    payload_bool = n * p_num * 4          # (N, P) int32 psum
    payload_packed = n * w * 4            # (N, W) uint32 OR
    ratio = payload_bool / payload_packed
    assert ratio >= 8, (
        f"bit-packed OR-reduce must move ≥8× fewer collective bytes, "
        f"got {ratio:.1f}× (P={p_num}, W={w})")

    d = len(jax.devices())
    if d >= 2:
        from jax.sharding import PartitionSpec as P

        mesh = compat.make_mesh((d,), ("shard",))
        rng = np.random.default_rng(5)
        vnew = jnp.asarray(rng.random((d, n, p_num)) < 0.02)

        def bool_body(b):
            return (jax.lax.psum(b[0].astype(jnp.int32), "shard") > 0)[None]

        def packed_body(b):
            words = compat.or_all_reduce(ne_ops.pack_bits(b[0]), "shard", d)
            return ne_ops.unpack_bits(words, p_num)[None]

        sm = dict(mesh=mesh, in_specs=(P("shard", None, None),),
                  out_specs=P("shard", None, None), check_vma=False)
        bool_fn = jax.jit(jax.shard_map(bool_body, **sm))
        packed_fn = jax.jit(jax.shard_map(packed_body, **sm))
        rb = jax.block_until_ready(bool_fn(vnew))
        rp = jax.block_until_ready(packed_fn(vnew))
        assert (np.asarray(rb) == np.asarray(rp)).all()
        t_b = timeit(lambda: jax.block_until_ready(bool_fn(vnew)),
                     repeats=repeats)
        t_q = timeit(lambda: jax.block_until_ready(packed_fn(vnew)),
                     repeats=repeats)
        timing = (f"devices={d};bool_us={t_b * 1e6:.1f};"
                  f"packed_us={t_q * 1e6:.1f};bit_identical=True")
    else:
        timing = "devices=1;collective_untimed=single_device"
        t_q = 0.0
    record("ne_or_reduce", t_q * 1e6,
           f"scale={scale};payload_bool_bytes={payload_bool};"
           f"payload_packed_bytes={payload_packed};"
           f"payload_reduction={ratio:.1f}x;{timing}")


def main(fast: bool = False, smoke: bool = False):
    _locality_row()
    # the ne_round rows stay at the reference scale even under --smoke
    # (the ≥8× payload assertion is the CI gate); only the repeat count
    # shrinks
    _ne_rows(scale=NE_SCALE, repeats=2 if (fast or smoke) else 5)


if __name__ == "__main__":
    main()
