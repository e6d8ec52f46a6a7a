"""ne_round kernel family: the XLA edge-list round (``one_hop``,
``select_chunk``, ``vertex_claims``) vs independent oracles, and the
Pallas (interpret) packing kernels vs their XLA and numpy twins —
all-integer math, so every comparison is exact.

Separate from test_kernels.py so none of this skips when hypothesis is
absent; the fuzz test guards its own import.
"""
import dataclasses
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partition
from repro.core.graph import as_graph
from repro.core.partitioner import (I32_INF, NEConfig, one_hop,
                                    priority_enc, select_chunk,
                                    vertex_claims)
from repro.dist.partitioner_sm import partition_spmd
from repro.graphs.generators import barabasi_albert
from repro.graphs.rmat import rmat
from repro.kernels.ne_round import ops as ne_ops
from repro.kernels.ne_round import ref as ne_ref

pytestmark = pytest.mark.kernels

ROOT = Path(__file__).resolve().parent.parent


def _rand_graph(n, m, seed):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2))
    return e[e[:, 0] != e[:, 1]]


# --------------------------------------------------------------------------
# one-hop allocation
# --------------------------------------------------------------------------

def _csr_chain_one_hop(g, vclaim, edge_part, p, mask=None):
    """Oracle: the min claim key over each edge's two directed CSR slots
    (``segment_min`` over 2M slots back to M edges)."""
    m = g.num_edges
    slot_key = vclaim[g.slot_src]
    slot_ok = (slot_key < I32_INF) & (edge_part[g.adj_eid] < 0)
    if mask is not None:
        slot_ok &= mask[g.adj_eid]
    ekey = jax.ops.segment_min(jnp.where(slot_ok, slot_key, I32_INF),
                               g.adj_eid, num_segments=m)
    new = ekey < I32_INF
    part = jnp.where(new, ekey % p, -1)
    counts = jnp.zeros((p,), jnp.int32).at[jnp.where(new, part, 0)].add(
        new.astype(jnp.int32))
    return part, counts


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("graph,p,seed", [
    ("rand-50-200", 4, 0), ("rand-300-1000", 8, 1), ("rand-128-500", 16, 2),
    ("ba-200-3", 8, 4)])
def test_one_hop_matches_segment_min_chain(graph, p, seed, masked):
    """The edge-list one-hop == the CSR-slot segment_min chain (each
    undirected edge owns two slots), with and without the SPMD padding
    mask."""
    kind, n, m = graph.split("-")
    e = (barabasi_albert(int(n), int(m), seed=3) if kind == "ba"
         else _rand_graph(int(n), int(m), seed))
    g = as_graph(e)
    n, m = g.num_vertices, g.num_edges
    rng = np.random.default_rng(seed)
    # claim keys: unclaimed or priority_enc keys of a few partitions
    vclaim = np.full(n, I32_INF, np.int32)
    cl = rng.integers(0, n, n // 2)
    vclaim[cl] = priority_enc(jnp.asarray(rng.integers(0, 50, cl.size)),
                              jnp.asarray(rng.integers(0, p, cl.size)), p)
    vclaim = jnp.asarray(vclaim)
    ep = jnp.asarray(np.where(rng.random(m) < 0.3, 0, -1).astype(np.int32))
    mask = jnp.asarray(rng.random(m) < 0.9) if masked else None
    got_part, got_counts = one_hop(vclaim, g.edges[:, 0], g.edges[:, 1], ep,
                                   p, mask=mask)
    want_part, want_counts = _csr_chain_one_hop(g, vclaim, ep, p, mask=mask)
    np.testing.assert_array_equal(np.asarray(got_part),
                                  np.asarray(want_part))
    np.testing.assert_array_equal(np.asarray(got_counts),
                                  np.asarray(want_counts))


# --------------------------------------------------------------------------
# boundary selection + claims
# --------------------------------------------------------------------------

def _np_select(vparts_c, active_c, degree_rest, lam, k_sel, remaining_c):
    """Oracle for the deterministic part of ``select_chunk``: per
    partition, its k_eff smallest-D_rest boundary vertices (ties by vertex
    id) whose D_rest prefix sum fits the remaining capacity (the first
    always).  Returns a list of selected-vertex lists, None for a row
    whose boundary is empty (a random re-seed)."""
    out = []
    for bnd_row, act, rem in zip(vparts_c, active_c, remaining_c):
        if not act:
            out.append([])
            continue
        cand = np.flatnonzero(bnd_row & (degree_rest > 0))
        if cand.size == 0:
            out.append(None)
            continue
        order = cand[np.lexsort((cand, degree_rest[cand]))]
        k_eff = int(np.clip(np.ceil(np.float32(lam) * np.float32(cand.size)),
                            1, k_sel))
        order = order[:k_eff]
        fits = np.cumsum(degree_rest[order]) <= rem
        fits[0] = True
        out.append(order[fits].tolist())
    return out


@pytest.mark.parametrize("n,c,k_sel,seed", [(100, 4, 16, 0), (600, 8, 64, 1),
                                            (257, 3, 32, 2)])
def test_select_chunk_matches_numpy_oracle(n, c, k_sel, seed):
    """``select_chunk`` == a numpy sort-and-prefix oracle; an empty
    boundary re-seeds from a vertex that still has unallocated edges."""
    rng = np.random.default_rng(seed)
    vparts_c = rng.random((c, n)) < 0.15
    vparts_c[0] = False                       # one re-seed row
    active_c = rng.random(c) < 0.8
    active_c[0] = True
    degree_rest = rng.integers(0, 20, n).astype(np.int32)
    remaining_c = rng.integers(0, 200, c).astype(np.int32)
    keys_c = jax.vmap(jax.random.PRNGKey)(jnp.arange(c) + seed)
    idx, valid = select_chunk(jnp.asarray(vparts_c), jnp.asarray(active_c),
                              jnp.asarray(degree_rest), 0.5, k_sel, keys_c,
                              jnp.asarray(remaining_c))
    idx, valid = np.asarray(idx), np.asarray(valid)
    want = _np_select(vparts_c, active_c, degree_rest, 0.5, k_sel,
                      remaining_c)
    for row, w in enumerate(want):
        got = idx[row][valid[row]].tolist()
        if w is None:
            assert len(got) == 1 and degree_rest[got[0]] > 0
        else:
            assert got == w, row


@pytest.mark.parametrize("sel_chunk", [1, 3, 8])
def test_vertex_claims_matches_numpy_oracle(sel_chunk):
    """``vertex_claims`` == the scatter-min of priority keys over each
    partition's ``select_chunk`` selection, for any selection chunking
    (every partition draws from its own fold_in key)."""
    rng = np.random.default_rng(7)
    n, p = 400, 8
    vparts = jnp.asarray(rng.random((n, p)) < 0.1)
    degree_rest = jnp.asarray(rng.integers(0, 15, n).astype(np.int32))
    epp = rng.integers(0, 300, p).astype(np.int32)
    sub = jax.random.PRNGKey(9)
    cfg = NEConfig(num_partitions=p, seed=0, k_sel=32, sel_chunk=sel_chunk)
    got = np.asarray(vertex_claims(cfg, 500, vparts, degree_rest,
                                   jnp.asarray(epp), sub))
    keys = jax.vmap(lambda i: jax.random.fold_in(sub, i))(
        jnp.arange(p, dtype=jnp.int32))
    idx, valid = select_chunk(vparts.T, jnp.asarray(epp <= 500), degree_rest,
                              cfg.lam, cfg.k_sel, keys,
                              jnp.asarray(500 - epp))
    want = np.full(n, I32_INF, np.int64)
    for part in range(p):
        sel = np.asarray(idx[part])[np.asarray(valid[part])]
        np.minimum.at(want, sel, int(priority_enc(epp[part], part, p)))
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# bit-packed replica sets
# --------------------------------------------------------------------------

@pytest.mark.parametrize("p", [1, 8, 31, 32, 33, 64, 100])
def test_pack_unpack_roundtrip(p):
    rng = np.random.default_rng(p)
    b = rng.random((57, p)) < 0.3
    words = ne_ops.pack_bits(jnp.asarray(b))
    assert words.shape == (57, ne_ops.replica_words(p))
    assert words.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(ne_ops.unpack_bits(words, p)), b)
    # jnp ref / numpy host twins agree with the kernel bit layout
    np.testing.assert_array_equal(np.asarray(words),
                                  ne_ref.pack_bits_np(b))
    np.testing.assert_array_equal(
        np.asarray(ne_ref.pack_bits_ref(jnp.asarray(b))), np.asarray(words))
    np.testing.assert_array_equal(ne_ref.unpack_bits_np(np.asarray(words),
                                                        p), b)


def test_or_words_equals_bool_or():
    rng = np.random.default_rng(0)
    a = rng.random((40, 37)) < 0.2
    b = rng.random((40, 37)) < 0.2
    merged = ne_ops.or_words(ne_ops.pack_bits(jnp.asarray(a)),
                             ne_ops.pack_bits(jnp.asarray(b)))
    np.testing.assert_array_equal(np.asarray(ne_ops.unpack_bits(merged, 37)),
                                  a | b)


def test_pack_fuzz_odd_widths():
    """Hypothesis fuzz over P not divisible by 32 (skips w/o hypothesis)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(1, 80),
           p=st.integers(1, 130).filter(lambda x: x % 32 != 0),
           seed=st.integers(0, 99))
    def inner(n, p, seed):
        rng = np.random.default_rng(seed)
        a = rng.random((n, p)) < 0.4
        b = rng.random((n, p)) < 0.4
        wa = ne_ref.pack_bits_np(a)
        assert wa.shape == (n, (p + 31) // 32)
        np.testing.assert_array_equal(ne_ref.unpack_bits_np(wa, p), a)
        merged = ne_ref.unpack_bits_np(wa | ne_ref.pack_bits_np(b), p)
        np.testing.assert_array_equal(merged, a | b)

    inner()


# --------------------------------------------------------------------------
# whole-run bit-identity + switches
# --------------------------------------------------------------------------

def test_partition_pallas_bit_identical_rmat():
    """Packed replica sets (use_pallas) == bool ones, through the SPMD
    round on one device."""
    g = rmat(10, 8, seed=13)
    cfg = NEConfig(num_partitions=8, seed=0, k_sel=64, edge_chunk=1 << 12,
                   use_pallas=False)
    r0 = partition_spmd(g, cfg, num_devices=1)
    r1 = partition_spmd(g, dataclasses.replace(cfg, use_pallas=True),
                        num_devices=1)
    np.testing.assert_array_equal(r0.edge_part, r1.edge_part)
    np.testing.assert_array_equal(r0.vparts, r1.vparts)
    np.testing.assert_array_equal(r0.edges_per_part, r1.edges_per_part)
    assert r0.rounds == r1.rounds
    # on one device the SPMD round is the single-controller round, which
    # ignores use_pallas
    r2 = partition(g, dataclasses.replace(cfg, use_pallas=True))
    np.testing.assert_array_equal(r2.edge_part, r0.edge_part)
    assert r2.rounds == r0.rounds


def test_ref_impl_env_switch(monkeypatch):
    """REPRO_NE_KERNELS=ref enables packing but routes it to pure XLA."""
    monkeypatch.setenv("REPRO_NE_KERNELS", "ref")
    assert ne_ops.env_enabled() and ne_ops.use_ref_impl()
    cfg = NEConfig(num_partitions=4)
    assert cfg.use_pallas is True
    g = barabasi_albert(120, 3, seed=1)
    r_env = partition_spmd(g, NEConfig(num_partitions=4, seed=0, k_sel=16),
                           num_devices=1)
    monkeypatch.delenv("REPRO_NE_KERNELS")
    assert not ne_ops.env_enabled()
    r_ref = partition_spmd(g, NEConfig(num_partitions=4, seed=0, k_sel=16,
                                       use_pallas=False), num_devices=1)
    np.testing.assert_array_equal(r_env.edge_part, r_ref.edge_part)
    np.testing.assert_array_equal(r_env.vparts, r_ref.vparts)


def test_core_and_io_stay_pallas_free():
    """Tier-1 never imports Pallas TPU lowering through repro.core /
    repro.io / the dist partitioner: the ops front door defers the kernel
    module until a pallas-dispatching call actually runs."""
    code = (
        "import sys\n"
        "import repro.core.partitioner, repro.core.graph\n"
        "import repro.dist.partitioner_sm\n"
        "import repro.io.edgefile, repro.io.stream\n"
        "from repro.kernels.ne_round import ops\n"
        "bad = [m for m in sys.modules if 'pallas' in m]\n"
        "assert not bad, f'pallas imported at module load: {bad}'\n"
        "print('CLEAN')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
        env={**__import__('os').environ,
             "PYTHONPATH": str(ROOT / "src")})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CLEAN" in proc.stdout
