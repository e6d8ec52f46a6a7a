"""The quality/scale shoot-out: partitioner × graph × P matrix.

Paper Fig. 8 generalized to the Schlag et al. 2018 evaluation standard:
one row per (partitioner, graph, P) cell reporting replication factor,
edge balance, vertex balance (``rf``/``eb``/``vb`` metrics — first-class
fields the driver's ``--compare`` quality gate diffs), wall-clock
(``us_per_call``), and — on the anchor cells — child-process peak RSS
partitioning from the on-disk canonical EdgeFile (``rss_kb``).

Partitioners: the paper's Distributed NE, the HEP-style ``hybrid`` at
two memory budgets (``repro.core.hybrid``), and the five
``core.baselines`` methods.  Graphs: RMAT scale 14, the ingested "real"
graph (``$REPRO_REAL_GRAPH`` — a downloaded SNAP edge-list text file —
or, when unset, a deterministic power-law graph round-tripped through
SNAP text so the ``repro.io.ingest`` path runs either way), plus denser
RMAT / power-law / road-like graphs in full (nightly) mode.

The fast-mode matrix *asserts* the PR's comparative claims on both
anchor graphs at P=16 — hybrid RF ≤ grid RF at every budget, hybrid RF
within :data:`RF_VS_NE_MAX`× NE RF at the tightest budget, and hybrid
peak RSS strictly below NE's — so the CI quality job fails on any PR
that breaks them, not just on drift vs the committed baseline.
"""
import os
import tempfile
import time

import numpy as np

from benchmarks.common import child_peak_rss_kb, fmt_metrics, record
from repro.core import NEConfig, evaluate, partition
from repro.core.baselines import PARTITIONERS
from repro.core.hybrid import HybridConfig, partition_hybrid
from repro.graphs.generators import grid2d, powerlaw_configuration
from repro.graphs.rmat import rmat
from repro.io.ingest import dump_text, ingest_text
from repro.io.stream import canonicalize_stream, graph_from_edgefile

HYBRID_BUDGETS = (0.5, 0.25)    # τ grid; last = tightest (asserted cell)
RF_VS_NE_MAX = 1.3              # tightest-budget hybrid RF vs NE bound
ANCHOR_P = 16                   # the partition count the claims assert at

# the RSS child runs on the CPU, never on a chip this process may hold;
# its rows say so (rss_platform)
_CHILD = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from repro.io.edgefile import EdgeFile
ef = EdgeFile({path!r})
{body}
assert (res.edge_part >= 0).all()
"""

_NE_BODY = """
from repro.core import NEConfig, partition
res = partition(ef, NEConfig(num_partitions={p}, seed=0))
"""

_HY_BODY = """
from repro.core.hybrid import HybridConfig, partition_hybrid
res = partition_hybrid(ef, HybridConfig(num_partitions={p},
                                        budget_frac={tau}, seed=0))
"""


def _real_graph(workdir: str):
    """The "real" slot: ingest ``$REPRO_REAL_GRAPH`` (a downloaded SNAP
    whitespace edge-list, optionally .gz) when set; otherwise dump a
    deterministic power-law graph as SNAP text and ingest that — the
    bundled fallback keeps the matrix (and the committed baseline)
    runnable offline while still exercising text ingest end to end."""
    src = os.environ.get("REPRO_REAL_GRAPH")
    if not src:
        g0 = powerlaw_configuration(30_000, 2.1, seed=4)
        src = os.path.join(workdir, "real.txt.gz")
        dump_text(np.asarray(g0.edges), src,
                  header="bundled power-law fallback — set "
                         "REPRO_REAL_GRAPH to a downloaded edge list")
    ef = ingest_text(src, os.path.join(workdir, "real.edges"),
                     tmpdir=workdir)
    return graph_from_edgefile(ef), ef


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def main(parts=(4, 16, 64), fast: bool = False):
    parts = parts[:2] if fast else parts
    with tempfile.TemporaryDirectory(prefix="bench_quality_") as td:
        real_g, real_ef = _real_graph(td)
        rmat_g = rmat(14, 16, seed=1)
        rmat_ef = canonicalize_stream(
            np.asarray(rmat_g.edges), os.path.join(td, "rmat.edges"),
            num_vertices=rmat_g.num_vertices, tmpdir=td)
        # anchor graphs carry an on-disk EdgeFile: their P=16 ne/hybrid
        # cells measure child peak RSS from the store, and the fast-mode
        # claims assert on them
        graphs = {"rmat_s14_ef16": (rmat_g, rmat_ef),
                  "real": (real_g, real_ef)}
        if not fast:
            graphs["rmat_s14_ef64"] = (rmat(14, 64, seed=2), None)
            graphs["plaw_a22"] = (
                powerlaw_configuration(50_000, 2.2, seed=4), None)
            graphs["road_grid2d"] = (grid2d(362, 362), None)

        failures = []
        ne_wins, cells = 0, 0
        for gname, (g, ef) in graphs.items():
            e = np.asarray(g.edges)
            for p in parts:
                rf = {}
                rss = {}
                anchor = ef is not None and p == ANCHOR_P

                def cell(method, run, rss_body=None):
                    res_part, us = _timed(run)
                    st = evaluate(e, res_part, g.num_vertices, p)
                    rf[method] = st.replication_factor
                    metrics = dict(rf=st.replication_factor,
                                   eb=st.edge_balance,
                                   vb=st.vertex_balance)
                    derived = ""
                    if anchor and rss_body is not None:
                        rss[method] = child_peak_rss_kb(
                            _CHILD.format(path=ef.path, body=rss_body))
                        metrics["rss_kb"] = rss[method]
                        derived = ";rss_platform=cpu"
                    record(f"quality_{gname}_p{p}_{method}", us,
                           fmt_metrics(**metrics) + derived)

                cell("ne",
                     lambda: partition(
                         g, NEConfig(num_partitions=p, seed=0)).edge_part,
                     _NE_BODY.format(p=p))
                for tau in HYBRID_BUDGETS:
                    # RSS children only for the tightest budget — that is
                    # the asserted pair, and each child pays a full
                    # interpreter + jax import
                    cell(f"hybrid_t{int(tau * 100)}",
                         lambda tau=tau: partition_hybrid(
                             g, HybridConfig(num_partitions=p,
                                             budget_frac=tau,
                                             seed=0)).edge_part,
                         _HY_BODY.format(p=p, tau=tau)
                         if tau == HYBRID_BUDGETS[-1] else None)
                for bname, fn in PARTITIONERS.items():
                    cell(bname, lambda fn=fn: fn(g, p))

                cells += 1
                ne_wins += rf["ne"] <= min(
                    v for k, v in rf.items() if k != "ne")
                if anchor:
                    failures += _check_claims(gname, p, rf, rss)

        record("quality_summary", 0.0,
               fmt_metrics(cells=cells, ne_best=ne_wins))
        if failures:
            raise AssertionError("; ".join(failures))


def _check_claims(gname: str, p: int, rf: dict, rss: dict) -> list:
    """The PR's comparative claims on an anchor cell — returned (not
    raised) so every cell still reports its rows before the suite
    fails, and the failure message names every broken claim at once."""
    out = []
    tight = f"hybrid_t{int(HYBRID_BUDGETS[-1] * 100)}"
    for tau in HYBRID_BUDGETS:
        hm = f"hybrid_t{int(tau * 100)}"
        if rf[hm] > rf["grid"] + 1e-9:
            out.append(f"{gname} p{p}: {hm} rf {rf[hm]:.4f} > grid "
                       f"rf {rf['grid']:.4f}")
    if rf[tight] > RF_VS_NE_MAX * rf["ne"]:
        out.append(f"{gname} p{p}: {tight} rf {rf[tight]:.4f} > "
                   f"{RF_VS_NE_MAX}x ne rf {rf['ne']:.4f}")
    if tight in rss and rss[tight] >= rss["ne"]:
        out.append(f"{gname} p{p}: {tight} peak rss {rss[tight]}KiB >= "
                   f"ne {rss['ne']}KiB")
    return out


if __name__ == "__main__":
    main()
