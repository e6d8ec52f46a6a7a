"""Paper Table 5: effect of partitioning on distributed graph applications.

Runs PageRank / SSSP / WCC on the vertex-cut engine over partitions from
each method and reports (a) exact per-superstep communication volume
(2·Σ|V(E_p)|·F — the engine's wire bytes) and (b) wall time.  Claim
validated: Distributed NE's lower RF translates 1:1 into lower COM, most
visible for communication-heavy PageRank (paper §7.6).

The engine needs one device per partition, so the measurement runs in a
subprocess with 8 forced host devices (same pattern as tests/test_spmd),
pinned to the CPU: this process may already hold the chip, and a child
that asked for it would fail or hang.  Rows say ``platform=cpu``.
"""
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import record


def _inner(fast: bool):
    import numpy as np
    import jax

    from benchmarks.common import timeit
    from repro.apps.algorithms import pagerank, sssp, wcc
    from repro.apps.engine import build_sharded_graph
    from repro.core import NEConfig, evaluate, partition
    from repro.core.baselines import grid_2d, random_1d
    from repro.core.metrics import comm_volume_model
    from repro.graphs.generators import barabasi_albert

    g = barabasi_albert(3_000 if fast else 8_000, 5, seed=11)
    e = np.asarray(g.edges)
    p = len(jax.devices())
    methods = {
        "dne": partition(g, NEConfig(num_partitions=p, seed=0,
                                     edge_chunk=1 << 14)).edge_part,
        "random": random_1d(g, p),
        "grid": grid_2d(g, p),
    }
    for name, ep in methods.items():
        st = evaluate(e, ep, g.num_vertices, p)
        sg = build_sharded_graph(e, ep, g.num_vertices, p)
        com_pr = comm_volume_model(st, g.num_vertices, 1) * 30
        t_pr = timeit(lambda: pagerank(sg, iters=30), repeats=1, warmup=1)
        t_ss = timeit(lambda: sssp(sg, source=0), repeats=1, warmup=1)
        t_wc = timeit(lambda: wcc(sg), repeats=1, warmup=1)
        print(f"CSV:table5_{name},{t_pr * 1e6:.1f},"
              f"rf={st.replication_factor:.2f};com_pr_MB={com_pr/1e6:.2f};"
              f"t_pr={t_pr:.2f}s;t_sssp={t_ss:.2f}s;t_wcc={t_wc:.2f}s;"
              f"platform={jax.devices()[0].platform}",
              flush=True)


def main(fast: bool = False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    root = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = f"{root / 'src'}:{root}"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_apps", "--inner"]
        + (["--fast"] if fast else []),
        capture_output=True, text=True, timeout=1800, env=env, cwd=root)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    for line in proc.stdout.splitlines():
        if line.startswith("CSV:"):
            name, us, derived = line[4:].split(",", 2)
            record(name, float(us), derived)


if __name__ == "__main__":
    if "--inner" in sys.argv:
        _inner("--fast" in sys.argv)
    else:
        main("--fast" in sys.argv)
