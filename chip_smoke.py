#!/usr/bin/env python
"""Chip smoke test: partition a Graph500 graph on a TPU, check the result.

    python chip_smoke.py                # one chip: the run + the reference
    python chip_smoke.py --four-chips   # the 4-chip mesh path only

One chip: a Graph500 Kronecker graph (scale 19, edge factor 16, seeded by
``--seed``) goes through the normal entry points — ``spill_canonical_rmat``
→ ``PartitionDriver(num_devices=1)`` → ``run`` → ``save_artifact`` →
``load_artifact`` — at P=16.  Host-side numpy checks hold the result to the
partitioning invariants on the full graph; where a CPU run of the same
graph is recorded (``RECORDED``), the chip must reproduce it edge for edge.
At scale 14 the same program must match a CPU child run edge for edge, and
``partition()`` on the chip must reproduce the
``quality_rmat_s14_ef16_p16_ne`` cell of BENCH_QUALITY.json (the CPU
numbers the CI quality gate holds) exactly.  The round count grows with
the vertex count and the seconds per round with the edge count, so the
run time is about quadratic in the scale; scale 19 (about 5 minutes on a
TPU v5e) is the largest measured whole inside the 20-minute limit
(PERF.md).

``--four-chips`` runs the same pipeline over a 4-device mesh, checks its
invariants and its rf against the single-device rf of the same graph
(within the 0.02 SPMD parity), and checks edge-for-edge identity at scale
14 against the same 4-shard program on 4 virtual CPU devices.

The CPU references come from a child pinned to the CPU that starts before
this process touches JAX.  The script fails, printing no result, where
JAX finds no TPU: it never runs on the CPU.  Any failed check raises.  The
last stdout line is the JSON result.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

P_NUM = 16
EDGE_FACTOR = 16
DEFAULT_SCALE = 19
REF_CELL = "quality_rmat_s14_ef16_p16_ne"   # rmat(14, 16, seed=1), seed=0
REF_SCALE, REF_GRAPH_SEED = 14, 1
RF_PARITY = 0.02    # SPMD vs single-controller rf (CHANGES.md)
# PartitionDriver(num_devices=1) on the CPU (JAX 0.9.0) of the smoke graph
# at (scale, seed): the chip must reproduce it edge for edge
RECORDED = {
    (19, 0): dict(m=7740051, rounds=200, rf=1.3498783111572266,
                  eb=1.1000010206651094, vb=1.6776685859620615,
                  edge_part_sha256="d73634b4ad579e767b9511931e3aee25"
                                   "e5b5bc1452b2876458fd7a7b6ce9ad28"),
}


def log(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")
    log(check=what.replace(" ", "_"), ok=True)


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events."""

    def __init__(self, jax):
        self.secs, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = dict(compile_s=round(self.secs, 3), cache_hits=self.hits,
                   cache_misses=self.misses)
        self.secs, self.hits, self.misses = 0.0, 0, 0
        return out


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def check_invariants(res, edges, n: int, cfg, tag: str) -> None:
    """Host-side (numpy) invariants of a finalized result on its graph."""
    import numpy as np

    from repro.core.metrics import evaluate

    p, m = cfg.num_partitions, edges.shape[0]
    ep = np.asarray(res.edge_part)
    check(ep.shape == (m,) and ep.min() >= 0 and ep.max() < p,
          f"{tag} every edge in exactly one partition in [0,P)")
    counts = np.bincount(ep, minlength=p)
    check(np.array_equal(counts, res.edges_per_part),
          f"{tag} edges_per_part counts the assignment")
    degree = (np.bincount(edges[:, 0], minlength=n)
              + np.bincount(edges[:, 1], minlength=n))
    # the paper's soft cap (Alg. 1): a partition stops expanding once past
    # alpha*M/P, so it overshoots by at most one vertex's edges
    cap = cfg.alpha * m / p + degree.max() + 1
    log(tag=tag, max_part_edges=int(counts.max()),
        alpha_limit=int(cfg.alpha * m / p), soft_cap=int(cap))
    check(counts.max() <= cap, f"{tag} edges_per_part within the alpha cap")
    vp = np.zeros((n, p), bool)
    vp[edges[:, 0], ep] = True
    vp[edges[:, 1], ep] = True
    check(np.array_equal(vp, res.vparts),
          f"{tag} replica map equals the one recomputed from edge_part")
    ref = evaluate(edges, ep, n, p)
    check(res.stats == ref, f"{tag} stats agree with metrics.evaluate")
    check(res.rounds < cfg.max_rounds, f"{tag} rounds below max_rounds")


def partition_graph(ef, n: int, cfg, num_devices: int, meter, tag: str):
    """EdgeFile → PartitionDriver → finalized result, timed by phase."""
    from repro.runtime import PartitionDriver

    drv, t_ingest = timed(lambda: PartitionDriver(ef, cfg,
                                                  num_devices=num_devices))
    _, t_first = timed(drv.step)
    first = meter.take()

    def loop():
        while not drv.done:
            drv.step()

    _, t_rounds = timed(loop)
    res, t_fin = timed(drv.finalize)
    st = res.stats
    log(tag=tag, devices=num_devices, n=n, m=drv.m, P=cfg.num_partitions,
        rounds=res.rounds, max_rounds=cfg.max_rounds, leftover=res.leftover,
        rf=st.replication_factor, eb=st.edge_balance,
        vb=st.vertex_balance)
    log(tag=tag, ingest_s=round(t_ingest, 3), first_round_s=round(t_first, 3),
        **first, rounds_s=round(t_rounds, 3),
        round_mean_s=round(t_rounds / max(res.rounds - 1, 1), 4),
        finalize_s=round(t_fin, 3))
    check(all(d.platform == "tpu" for d in drv.mesh.devices.flat),
          f"{tag} mesh is on the chip")
    return drv, res


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def spill_graph(args, out: Path):
    from repro.io.spill import spill_canonical_rmat

    ef, t_spill = timed(lambda: spill_canonical_rmat(
        out / "graph", args.scale, EDGE_FACTOR, seed=args.seed))
    log(scale=args.scale, edge_factor=EDGE_FACTOR, seed=args.seed,
        n=int(ef.num_vertices), m=int(ef.num_edges),
        spill_s=round(t_spill, 3))
    return ef, int(ef.num_vertices)


def run_one_chip(args, out: Path, meter, child) -> None:
    import jax
    import numpy as np

    from repro.core import NEConfig, partition
    from repro.graphs.rmat import rmat
    from repro.runtime import load_artifact

    cfg = NEConfig(num_partitions=P_NUM)
    ef, n = spill_graph(args, out)
    drv, res = partition_graph(ef, n, cfg, 1, meter, "one_chip")
    art_dir = out / "artifact"
    _, t_save = timed(lambda: drv.save_artifact(art_dir))
    art, t_load = timed(lambda: load_artifact(art_dir))
    log(artifact_save_s=round(t_save, 3), artifact_load_s=round(t_load, 3),
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))

    edges = ef.read_all()
    check_invariants(res, edges, n, drv.cfg, "one_chip")
    check(np.array_equal(art.edge_part, res.edge_part)
          and np.array_equal(art.vparts, res.vparts)
          and np.array_equal(art.edges, edges)
          and np.array_equal(art.edges_per_part, res.edges_per_part)
          and art.rounds == res.rounds,
          "loaded artifact round-trips the result")
    del drv, art
    rec = RECORDED.get((args.scale, args.seed))
    if rec is not None:
        st = res.stats
        got = dict(m=int(ef.num_edges), rounds=res.rounds,
                   rf=st.replication_factor, eb=st.edge_balance,
                   vb=st.vertex_balance,
                   edge_part_sha256=edge_part_digest(res.edge_part))
        log(**{f"recorded_{k}": v for k, v in rec.items() if got[k] != v})
        check(got == rec, "one-chip run edge-for-edge identical to the "
              "recorded CPU run of the same graph")

    # the same program on the CPU, edge for edge, at scale 14
    check_cpu_identity(out / "cpu_reference.npz", child, 1)

    # the CI quality gate's reference cell, on the chip
    want = next(r["metrics"] for r in json.loads(
        (ROOT / "BENCH_QUALITY.json").read_text()) if r["name"] == REF_CELL)
    g = rmat(REF_SCALE, EDGE_FACTOR, seed=REF_GRAPH_SEED)
    # the cell was recorded with JAX's legacy threefry bit stream (the
    # default before JAX 0.5); the installed default draws other random
    # re-seed vertices, so the same run on the CPU gives rf 1.93335
    with jax.threefry_partitionable(False):
        ref, t_ref = timed(lambda: partition(
            g, NEConfig(num_partitions=P_NUM, seed=0)))
    st = ref.stats
    got = {"rf": st.replication_factor, "eb": st.edge_balance,
           "vb": st.vertex_balance}
    log(reference=REF_CELL, rounds=ref.rounds, partition_s=round(t_ref, 3),
        **got, **meter.take())
    # BENCH_QUALITY.json records 6 significant digits (fmt_metrics)
    check(all(float(f"{got[k]:.6g}") == want[k] for k in got),
          f"{REF_CELL} rf/eb/vb match BENCH_QUALITY.json")


def cpu_reference(args) -> None:
    """Child mode, pinned to the CPU: the scale-14 ``PartitionDriver`` run
    on as many devices as the chip run uses, and, for the four-chip run
    of a graph with no recorded single-device rf, that rf."""
    import jax
    import numpy as np

    from repro import compile_cache
    from repro.core import NEConfig, partition
    from repro.graphs.rmat import rmat
    from repro.io.spill import spill_canonical_rmat
    from repro.runtime import PartitionDriver

    compile_cache.enable()
    num_dev = 4 if args.four_chips else 1
    assert jax.devices()[0].platform == "cpu"
    assert len(jax.devices()) == num_dev
    g = rmat(REF_SCALE, EDGE_FACTOR, seed=REF_GRAPH_SEED)
    res = PartitionDriver(g, NEConfig(num_partitions=P_NUM, seed=0),
                          num_devices=num_dev).run()
    out = Path(args.cpu_reference)
    rf_single = np.nan
    if args.four_chips and (args.scale, args.seed) not in RECORDED:
        ef = spill_canonical_rmat(out.parent / "graph_cpu", args.scale,
                                  EDGE_FACTOR, seed=args.seed)
        rf_single = partition(
            ef, NEConfig(num_partitions=P_NUM)).stats.replication_factor
    np.savez(out, edge_part=res.edge_part, vparts=res.vparts,
             rounds=res.rounds, rf_single=rf_single)


def check_cpu_identity(want_path: Path, child, num_dev: int):
    """The scale-14 ``PartitionDriver`` run on the chip(s), edge for edge
    against the CPU child's run of the same program."""
    import numpy as np

    from repro.core.partitioner import NEConfig
    from repro.graphs.rmat import rmat
    from repro.runtime import PartitionDriver

    g = rmat(REF_SCALE, EDGE_FACTOR, seed=REF_GRAPH_SEED)
    res = PartitionDriver(g, NEConfig(num_partitions=P_NUM, seed=0),
                          num_devices=num_dev).run()
    check(child.wait(timeout=900) == 0, "CPU reference child succeeded")
    want = np.load(want_path)
    log(scale14_devices=num_dev, scale14_rounds_tpu=res.rounds,
        scale14_rounds_cpu=int(want["rounds"]),
        scale14_rf=res.stats.replication_factor)
    check(np.array_equal(res.edge_part, want["edge_part"])
          and np.array_equal(res.vparts, want["vparts"])
          and res.rounds == int(want["rounds"]),
          f"scale-14 {num_dev}-chip run edge-for-edge identical to "
          f"{num_dev} CPU device(s)")
    return want


def edge_part_digest(edge_part) -> str:
    import numpy as np

    return hashlib.sha256(
        np.ascontiguousarray(edge_part, np.int32).tobytes()).hexdigest()


def run_four_chips(args, out: Path, meter, child) -> None:
    import jax

    from repro.core.partitioner import NEConfig

    check(len(jax.devices()) >= 4, "four chips present")
    cfg = NEConfig(num_partitions=P_NUM)
    ef, n = spill_graph(args, out)
    drv4, res4 = partition_graph(ef, n, cfg, 4, meter, "four_chips")
    check(len(drv4.state.edge_part.sharding.device_set) == 4,
          "edge shards placed across the 4-chip mesh")
    log(peak_bytes_in_use=[peak_bytes(d) for d in jax.devices()[:4]])
    check_invariants(res4, ef.read_all(), n, drv4.cfg, "four_chips")
    del drv4

    want = check_cpu_identity(out / "cpu_reference.npz", child, 4)
    rec = RECORDED.get((args.scale, args.seed))
    rf1 = rec["rf"] if rec else float(want["rf_single"])
    rf4 = res4.stats.replication_factor
    log(rf_four_chips=rf4, rf_single_device=rf1, rf_diff=abs(rf4 - rf1),
        rf_single_device_from="recorded CPU run" if rec else "CPU child")
    check(abs(rf4 - rf1) <= RF_PARITY,
          "4-chip rf within the SPMD-vs-single parity")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=DEFAULT_SCALE)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh path and its comparison")
    ap.add_argument("--out", default=str(ROOT / ".chip_smoke"),
                    help="graph, store and artifact (removed at exit)")
    ap.add_argument("--cpu-reference", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.cpu_reference:
        cpu_reference(args)
        return 0

    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    child = None
    try:
        # the CPU reference child starts before this process imports JAX
        # and is pinned to the CPU: it never asks for the chip this
        # process is about to take
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        cmd = [sys.executable, __file__, "--scale", str(args.scale),
               "--seed", str(args.seed), "--cpu-reference",
               str(out / "cpu_reference.npz")]
        if args.four_chips:
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            cmd.append("--four-chips")
        child = subprocess.Popen(cmd, env=env)
        import jax

        from repro import compile_cache

        devs = jax.devices()
        if devs[0].platform != "tpu":
            print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform});"
                  " this test runs only on the chip", file=sys.stderr)
            return 1
        log(platform=devs[0].platform, device_kind=repr(devs[0].device_kind),
            device_count=len(devs),
            compile_cache=compile_cache.enable())
        meter = CompileMeter(jax)
        if args.four_chips:
            run_four_chips(args, out, meter, child)
        else:
            run_one_chip(args, out, meter, child)
        devs = jax.devices()
        print(json.dumps({"ok": True, "device": {
            "platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}}))
        return 0
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
