"""Runtime driver: per-round snapshot overhead + resume/artifact latency.

Quantifies what the checkpointable state machine costs over the
fire-and-forget jit — the number that decides how often a production run
can afford to snapshot.  Rows:

  runtime/round_plain        per-round step time, no snapshots
  runtime/round_snap         per-round step time, snapshot every round
  runtime/live_overhead      per-round cost of the live metrics bus
                             (quality reduction + one flushed JSONL line);
                             smoke-gated at <5% and bit-identity
  runtime/snapshot_overhead  the delta — pure snapshot cost per round
  runtime/resume_restore     latency from PartitionDriver.resume() call to
                             a stepped-and-ready driver (ingest + restore)
  runtime/artifact_save      durable artifact write
  runtime/artifact_load      artifact load back to edge_part + replica map
  runtime/multihost_round    2-process × 4-device steady-state round time
                             (real jax.distributed collectives), vs the
                             single-process round in `derived`
  runtime/multihost_snap     per-round cost of the multi-writer snapshot
                             publish protocol (2-process, snapshot_every=1
                             minus snapshot_every=0)

In ``--smoke`` mode this suite is also the CI resume drift gate: it
asserts the resumed run reproduces the uninterrupted assignment bit for
bit and that the artifact round-trips, so any regression in the
runtime layer breaks the gate loudly.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from benchmarks.common import record

from repro.core import NEConfig
from repro.graphs.rmat import rmat
from repro.runtime import PartitionDriver, load_artifact

ROOT = Path(__file__).resolve().parents[1]


def _multihost_run(td: Path, ef_path: str, name: str,
                   snapshot_every: int) -> dict:
    """One 2-process × 4-device launcher invocation; returns timing.json.

    The gang runs on the CPU (``JAX_PLATFORMS=cpu``): two local workers
    cannot share a chip, and this process may already hold it."""
    out_dir = td / f"mh_{name}"
    args = [sys.executable, str(ROOT / "scripts" / "launch_multihost.py"),
            "--edgefile", ef_path, "--partitions", "8", "--seed", "0",
            "--k-sel", "64", "--edge-chunk", str(1 << 12),
            "--num-processes", "2", "--devices-per-process", "4",
            "--snapshot-dir", str(td / f"snap_{name}"),
            "--snapshot-every", str(snapshot_every),
            "--out", str(out_dir), "--timeout", "900"]
    proc = subprocess.run(args, capture_output=True, text=True, timeout=1200,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        raise RuntimeError(f"multihost bench run failed "
                           f"(rc={proc.returncode}):\n{proc.stderr[-3000:]}")
    return json.loads((out_dir / "timing.json").read_text())


def bench_multihost(single_round_us: float, fast: bool = False):
    """2-process round latency + snapshot publish overhead rows.

    Spawns the same launcher CI's multihost job uses, on a spilled
    canonical store, so the row measures real ``jax.distributed``
    collectives + the cooperative snapshot publish — not a rehearsal.
    """
    from repro.io.spill import spill_canonical_rmat

    with tempfile.TemporaryDirectory() as td:
        td = Path(td)
        ef = spill_canonical_rmat(td / "graph", 9 if fast else 11, 8,
                                  seed=3, chunk_size=1 << 12)
        ef_path = str(ef.path)
        ef.close()
        plain = _multihost_run(td, ef_path, "plain", snapshot_every=0)
        snap = _multihost_run(td, ef_path, "snap", snapshot_every=1)
        t_plain = float(np.mean(plain["round_secs"][1:]))
        t_snap = float(np.mean(snap["round_secs"][1:]))
        record("runtime/multihost_round", t_plain * 1e6,
               f"rounds={plain['rounds']};"
               f"single_round_us={single_round_us:.1f};"
               f"platform={plain['platform']}")
        record("runtime/multihost_snap", (t_snap - t_plain) * 1e6,
               f"+{(t_snap - t_plain) / max(t_plain, 1e-12) * 100:.0f}%;"
               f"platform={snap['platform']}")


def main(fast: bool = False, smoke: bool = False):
    scale = 10 if fast else 12
    g = rmat(scale, 8, seed=3)
    cfg = NEConfig(num_partitions=8, seed=0, k_sel=128, edge_chunk=1 << 14)

    with tempfile.TemporaryDirectory() as td:
        # uninterrupted, no snapshots (warm compile happens on round 1;
        # steady-state rounds are what a long run pays per round)
        drv = PartitionDriver(g, cfg)
        drv.step()                              # compile
        t0 = time.time()
        res = drv.run()
        rounds = max(res.rounds - 1, 1)
        t_plain = (time.time() - t0) / rounds
        record("runtime/round_plain", t_plain * 1e6,
               f"rounds={res.rounds}")

        # tracing overhead: the identical run with the tracer streaming
        # its JSONL log.  The smoke gate asserts instrumentation stays
        # out of the round budget (<5%, with an absolute floor so
        # sub-ms smoke rounds don't gate on scheduler noise) and that
        # spans never alter the computed assignment.
        from repro.obs import trace as obs

        obs.configure(path=str(Path(td) / "trace" / obs.log_name(0)),
                      process=0, meta={"bench": "runtime"})
        drv_t = PartitionDriver(g, cfg)
        drv_t.step()
        t0 = time.time()
        res_t = drv_t.run()
        t_traced = (time.time() - t0) / max(res_t.rounds - 1, 1)
        obs.disable()
        record("runtime/trace_overhead", (t_traced - t_plain) * 1e6,
               f"+{(t_traced - t_plain) / max(t_plain, 1e-12) * 100:.2f}%")
        assert (res_t.edge_part == res.edge_part).all(), \
            "traced run diverged from untraced run"
        if smoke:
            slack = max(t_plain * 0.05, 5e-4)
            assert t_traced - t_plain <= slack, (
                f"tracing overhead {t_traced - t_plain:.6f}s/round exceeds "
                f"{slack:.6f}s (plain {t_plain:.6f}s)")

        # live-metrics overhead: the identical run with the metrics bus
        # publishing a per-round snapshot (one jitted quality reduction
        # + one flushed JSONL line).  Same gate as tracing: <5% of the
        # round budget and bit-identical output — monitoring a
        # production run must be free to turn on.
        from repro.obs import live as obs_live

        obs_live.configure(Path(td) / "live", process=0,
                           meta={"bench": "runtime"})
        drv_l = PartitionDriver(g, cfg)
        drv_l.step()
        t0 = time.time()
        res_l = drv_l.run()
        t_live = (time.time() - t0) / max(res_l.rounds - 1, 1)
        obs_live.disable()
        record("runtime/live_overhead", (t_live - t_plain) * 1e6,
               f"+{(t_live - t_plain) / max(t_plain, 1e-12) * 100:.2f}%")
        assert (res_l.edge_part == res.edge_part).all(), \
            "monitored run diverged from unmonitored run"
        if smoke:
            slack = max(t_plain * 0.05, 5e-4)
            assert t_live - t_plain <= slack, (
                f"live-metrics overhead {t_live - t_plain:.6f}s/round "
                f"exceeds {slack:.6f}s (plain {t_plain:.6f}s)")

        snap_dir = Path(td) / "snap"
        drv_s = PartitionDriver(g, cfg, snapshot_dir=snap_dir,
                                snapshot_every=1, keep=100_000)
        drv_s.step()
        t0 = time.time()
        res_s = drv_s.run()
        t_snap = (time.time() - t0) / max(res_s.rounds - 1, 1)
        record("runtime/round_snap", t_snap * 1e6,
               f"snapshots={len(drv_s.snapshot.rounds())}")
        record("runtime/snapshot_overhead", (t_snap - t_plain) * 1e6,
               f"+{(t_snap - t_plain) / max(t_plain, 1e-12) * 100:.0f}%")

        # resume latency: rebuild shards + restore state at round k
        k = max(res_s.rounds // 2, 1)
        t0 = time.time()
        drv_r = PartitionDriver.resume(g, cfg, snap_dir, round_k=k)
        t_resume = time.time() - t0
        record("runtime/resume_restore", t_resume * 1e6, f"round={k}")
        res_r = drv_r.run()

        art_dir = Path(td) / "art"
        t0 = time.time()
        drv_s.save_artifact(art_dir)
        record("runtime/artifact_save", (time.time() - t0) * 1e6,
               f"m={g.num_edges}")
        t0 = time.time()
        loaded = load_artifact(art_dir)
        ep = loaded.edge_part
        vp = loaded.vparts
        record("runtime/artifact_load", (time.time() - t0) * 1e6,
               f"bytes={sum(p.stat().st_size for p in art_dir.iterdir())}")

        # CI resume drift gate — a silent bit-identity regression in the
        # runtime layer must fail the smoke suite, not just a slow test
        ok_resume = bool((res_r.edge_part == res.edge_part).all()
                         and (res_r.vparts == res.vparts).all())
        ok_artifact = bool((ep == res_s.edge_part).all()
                           and (vp == res_s.vparts).all())
        record("runtime/resume_identical", float(ok_resume),
               f"round={k} vs full")
        record("runtime/artifact_identical", float(ok_artifact), "")
        assert ok_resume, "resumed run diverged from uninterrupted run"
        assert ok_artifact, "artifact did not round-trip the assignment"
        if not smoke:
            assert (res_s.edge_part == res.edge_part).all()

    bench_multihost(t_plain * 1e6, fast=fast)


if __name__ == "__main__":
    main()
