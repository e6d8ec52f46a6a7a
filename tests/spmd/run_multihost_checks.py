"""Multi-controller integration checks: N real processes x 8/N devices.

Drives ``scripts/launch_multihost.py`` (the exact entrypoint CI documents)
through the full failure matrix against a single-process 8-device
reference computed in this interpreter:

  A. uninterrupted N-process run         -> bit-identical to partition_spmd
     (traced + live metrics bus on; a monitor attached WHILE it runs
     must see every host heartbeat with strictly monotone rounds, and
     the last live replication factor must equal the finalized metric)
  B. kill worker 1 after the round-k snapshot published (job dies);
     a monitor attached to the dead bus must exit STALLED
  C. resume B                            -> bit-identical, from round k
  D. kill worker 1 mid-save (shards staged, never published)
  E. resume D                            -> bit-identical, from round k-1
                                            (the torn round is skipped)
  F. single-process driver resumes A's N-process snapshots (cross
     process-count restore compatibility)
  G. sharded finalize + cooperative artifact save, with edge_part
     materialization FORBIDDEN (env) -> the run completes and the
     artifact bytes are identical to a single-process save_artifact
  H. elastic resume of B's snapshots on the OTHER process count (2<->4,
     same 8 global devices) -> bit-identical, from round k
  I. elastic resume of A's snapshots on HALF the devices (8 -> 4,
     store-backed reshard) -> bit-identical final result

The process count comes from --procs / $MULTIHOST_PROCS (default 2; CI
runs a {2, 4} matrix) and the RMAT scale from --scale /
$MULTIHOST_SCALE (default 10; the nightly job runs 16).

Prints one ``RESULT {json}`` line and exits nonzero if any bit-identity
or protocol check fails, so it gates CI when run directly; the pytest
wrapper (tests/test_multihost.py, ``-m multihost``) asserts the same
fields for local runs.
"""
import argparse
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
SCRIPT = ROOT / "scripts" / "launch_multihost.py"
MONITOR = ROOT / "scripts" / "monitor_run.py"
sys.path.insert(0, str(ROOT / "src"))

ap = argparse.ArgumentParser()
ap.add_argument(
    "--procs", type=int, default=int(os.environ.get("MULTIHOST_PROCS", "2"))
)
ap.add_argument(
    "--scale", type=int, default=int(os.environ.get("MULTIHOST_SCALE", "10"))
)
cli = ap.parse_args()

import jax  # noqa: E402

from repro.core import NEConfig, evaluate  # noqa: E402
from repro.dist.partitioner_sm import partition_spmd  # noqa: E402
from repro.io.spill import spill_canonical_rmat  # noqa: E402
from repro.obs import export as obs_export  # noqa: E402
from repro.obs import live as obs_live  # noqa: E402
from repro.obs import monitor as obs_mon  # noqa: E402
from repro.obs import report as obs_report  # noqa: E402
from repro.runtime import PartitionDriver, save_artifact  # noqa: E402
from repro.runtime.snapshot import config_fingerprint  # noqa: E402
from repro.runtime.snapshot import graph_fingerprint  # noqa: E402

SCALE, EDGE_FACTOR = cli.scale, 8
PROCS = cli.procs
PROCS_ALT = 4 if PROCS == 2 else 2  # the elastic process-count twin
if 8 % PROCS or 8 % PROCS_ALT:
    raise SystemExit(f"--procs {PROCS} does not divide the 8-device mesh")
CFG = NEConfig(num_partitions=8, seed=0, k_sel=64, edge_chunk=1 << 12)

out = {"devices": len(jax.devices()), "procs": PROCS, "scale": SCALE}


def _launch_args(
    td, name, extra, expect_fail, procs, devices, with_out, env_extra
):
    procs = procs or PROCS
    if devices is None:
        devices = 8 // procs
    out_dir = td / f"out_{name}"
    args = [
        sys.executable,
        str(SCRIPT),
        "--edgefile",
        str(td / "graph" / "canonical.edges"),
        "--partitions",
        "8",
        "--seed",
        "0",
        "--k-sel",
        "64",
        "--edge-chunk",
        str(1 << 12),
        "--num-processes",
        str(procs),
        "--devices-per-process",
        str(devices),
        "--keep",
        "100000",
        "--log-dir",
        str(td / f"logs_{name}"),
        "--timeout",
        "900",
        *extra,
    ]
    if with_out and not expect_fail:
        args += ["--out", str(out_dir)]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    if env_extra:
        env.update(env_extra)
    return args, env, out_dir


def launch(
    td,
    name,
    extra,
    expect_fail=False,
    procs=None,
    devices=None,
    with_out=True,
    env_extra=None,
):
    """One parent invocation of the launcher; returns (rc, out_dir)."""
    args, env, out_dir = _launch_args(
        td, name, extra, expect_fail, procs, devices, with_out, env_extra
    )
    proc = subprocess.run(
        args, capture_output=True, text=True, timeout=1800, env=env
    )
    if not expect_fail and proc.returncode != 0:
        print(proc.stdout[-4000:], file=sys.stderr)
        print(proc.stderr[-4000:], file=sys.stderr)
        raise RuntimeError(f"run {name} failed rc={proc.returncode}")
    return proc.returncode, out_dir


def launch_async(td, name, extra, **kw):
    """Popen the launcher so a monitor can attach while it runs."""
    args, env, out_dir = _launch_args(
        td,
        name,
        extra,
        False,
        kw.get("procs"),
        kw.get("devices"),
        True,
        kw.get("env_extra"),
    )
    log_path = td / f"parent_{name}.log"
    with open(log_path, "w") as log_fh:  # child inherits the descriptor
        proc = subprocess.Popen(
            args, stdout=log_fh, stderr=subprocess.STDOUT, env=env
        )
    return proc, out_dir, log_path


def load(out_dir):
    res = np.load(out_dir / "result.npz")
    timing = json.loads((out_dir / "timing.json").read_text())
    return res, timing


def identical(res, ref):
    return bool(
        (res["edge_part"] == np.asarray(ref.edge_part)).all()
        and (res["vparts"] == np.asarray(ref.vparts)).all()
        and int(res["rounds"]) == int(ref.rounds)
    )


def dirs_identical(a: Path, b: Path) -> bool:
    names_a = sorted(p.name for p in a.iterdir())
    names_b = sorted(p.name for p in b.iterdir())
    if names_a != names_b:
        return False
    return all((a / n).read_bytes() == (b / n).read_bytes() for n in names_a)


with tempfile.TemporaryDirectory() as _td:
    td = Path(_td)
    ef = spill_canonical_rmat(
        td / "graph", SCALE, EDGE_FACTOR, seed=3, chunk_size=1 << 12
    )
    out["num_edges"] = int(ef.num_edges)

    # single-process 8-device reference, same canonical EdgeFile
    ref = partition_spmd(ef, CFG)
    out["ref_rounds"] = int(ref.rounds)
    k = max(int(ref.rounds) // 2, 1)
    out["kill_round"] = k

    # A: uninterrupted N-process run, launched TRACED and with the live
    # metrics bus on — bit-identity against the untraced, unmonitored
    # in-process reference (checked below) proves instrumentation never
    # perturbs the partition.  A monitor attaches WHILE the job runs:
    # the contract is >=1 heartbeat per host observed live, strictly
    # monotone rounds, and a healthy live-attach CLI verdict.
    trace_dir = td / "traceA"
    live_a = td / "liveA"
    proc_a, out_a, log_a = launch_async(
        td,
        "A",
        [
            "--snapshot-dir",
            str(td / "snapA"),
            "--snapshot-every",
            "1",
            "--trace-dir",
            str(trace_dir),
            "--metrics-dir",
            str(live_a),
        ],
    )
    # rounds can take arbitrarily long on first compile, so the stall
    # thresholds are effectively off — this attach checks
    # *observability*, not latency
    mon_cli = [
        sys.executable,
        str(MONITOR),
        str(live_a),
        "--once",
        "--json",
        "--stall-after",
        "1e9",
        "--dead-after",
        "1e9",
    ]
    mon = obs_mon.BusMonitor(
        live_a, obs_mon.MonitorConfig(stall_after=1e9, dead_after=1e9)
    )
    live_hb_seen = {}  # pid -> max hb seq observed while the job was alive
    live_cli_rc = None  # monitor_run.py --once verdict, attached mid-run
    deadline = time.time() + 1800
    while proc_a.poll() is None:
        if time.time() > deadline:
            proc_a.kill()
            raise RuntimeError("run A timed out")
        mon.poll()
        for pid, t in mon.tails.items():
            if t.last is not None:
                live_hb_seen[pid] = max(
                    live_hb_seen.get(pid, 0), int(t.last.get("seq") or 0)
                )
        if live_cli_rc is None and len(live_hb_seen) == PROCS:
            cp = subprocess.run(
                mon_cli, capture_output=True, text=True, timeout=120
            )
            live_cli_rc = cp.returncode
        time.sleep(0.2)
    if proc_a.returncode != 0:
        print(log_a.read_text()[-4000:], file=sys.stderr)
        raise RuntimeError(f"run A failed rc={proc_a.returncode}")
    mon.poll()
    if live_cli_rc is None:  # run finished before the attach window opened
        cp = subprocess.run(
            mon_cli, capture_output=True, text=True, timeout=120
        )
        live_cli_rc = cp.returncode
    final_live = mon.assess()
    res_a, timing_a = load(out_a)
    out["multihost_matches_spmd"] = identical(res_a, ref)
    out["multihost_rounds"] = int(res_a["rounds"])
    out["round_secs_mean"] = float(np.mean(timing_a["round_secs"][1:]))

    # live-monitor acceptance: every host heartbeat while the job was
    # still running, rounds strictly monotone, everyone reached done,
    # and the live-attached CLI judged the run healthy/done (exit 0)
    out["monitor_hosts_ok"] = bool(
        len(final_live["hosts"]) == PROCS
        and all(h["done"] for h in final_live["hosts"].values())
        and len(live_hb_seen) == PROCS
        and all(v >= 1 for v in live_hb_seen.values())
    )
    out["monitor_rounds_monotone"] = bool(
        mon.tails
        and all(
            t.rounds_monotone() and len(t.rounds_seen) >= 1
            for t in mon.tails.values()
        )
    )
    # the last round-phase gauge is computed from the replicated state
    # at the fixed point, so it must equal the finalized artifact metric
    last_rfs = [t.history[-1]["rf"] for t in mon.tails.values() if t.history]
    out["monitor_rf_matches_final"] = bool(
        len(last_rfs) == PROCS
        and all(
            abs(rf - timing_a["replication_factor"]) < 1e-6 for rf in last_rfs
        )
    )
    out["monitor_live_exit"] = live_cli_rc == 0

    # the traced run leaves the full telemetry artifact set: one JSONL
    # log per host, a merged Perfetto-loadable Chrome trace, and a
    # report with round percentiles, phase breakdown, collective payload
    # bytes and per-host peak RSS
    trace_logs = obs_export.host_logs(trace_dir)
    out["trace_per_host_logs"] = len(trace_logs) == PROCS
    merged_trace = td / "traceA_merged.json"
    trace = obs_export.write_chrome_trace(merged_trace, trace_dir)
    trace_evs = trace["traceEvents"]
    out["trace_chrome_valid"] = bool(
        merged_trace.exists()
        and len({e["pid"] for e in trace_evs}) == PROCS
        and any(
            e["ph"] == "X" and e["name"] == "round" for e in trace_evs
        )
        and any(
            e["ph"] == "X" and e["name"] == "ingest" for e in trace_evs
        )
    )
    rep = obs_report.summarize_run(trace_dir)
    out["report_fields_ok"] = bool(
        rep["rounds"] is not None
        and rep["rounds"]["count"] == int(res_a["rounds"]) * PROCS
        and 0 <= rep["rounds"]["p50_s"] <= rep["rounds"]["p99_s"]
        and "ingest" in rep["phases"]
        and "finalize" in rep["phases"]
        and rep["counters"]["sync_payload_bytes"]["last"] > 0
        and all(h.get("peak_rss_kb") for h in rep["hosts"].values())
    )
    art_dest = os.environ.get("MULTIHOST_ARTIFACTS")
    if art_dest:
        dest = Path(art_dest)
        dest.mkdir(parents=True, exist_ok=True)
        shutil.copy(merged_trace, dest / "trace_merged.json")
        (dest / "report.txt").write_text(obs_report.render(rep))
        for p in trace_logs:
            shutil.copy(p, dest / p.name)
        (dest / "dashboard.txt").write_text(
            obs_mon.render_dashboard(final_live)
        )
        for p in obs_live.host_metrics(live_a):
            shutil.copy(p, dest / p.name)

    # the sharded epilogue's collective-combined metrics == evaluate()
    # of the reference assignment
    ref_stats = evaluate(
        ef.read_all(),
        np.asarray(ref.edge_part),
        int(ef.num_vertices),
        CFG.num_partitions,
    )
    rf_got = timing_a.get("replication_factor", -1.0)
    eb_got = timing_a.get("edge_balance", -1.0)
    out["stats_match"] = bool(
        abs(rf_got - ref_stats.replication_factor) < 1e-12
        and abs(eb_got - ref_stats.edge_balance) < 1e-12
    )

    # B: worker 1 dies right after the round-k snapshot publishes
    rc_b, _ = launch(
        td,
        "B",
        [
            "--snapshot-dir",
            str(td / "snapB"),
            "--snapshot-every",
            "1",
            "--die-round",
            str(k),
            "--die-stage",
            "after-publish",
            "--die-process",
            "1",
            "--metrics-dir",
            str(td / "liveB"),
        ],
        expect_fail=True,
    )
    out["kill_job_failed"] = rc_b != 0
    published_b = sorted(p.name for p in (td / "snapB").glob("step_*"))
    out["kill_last_published"] = (
        int(published_b[-1].split("_")[1]) if published_b else 0
    )

    # the killed gang leaves the bus with heartbeats but no done marker:
    # a monitor attached to its ruins must flip to STALLED (exit 4) —
    # streams exist, so the run is not dead, but no host is progressing
    cp = subprocess.run(
        [
            sys.executable,
            str(MONITOR),
            str(td / "liveB"),
            "--once",
            "--stall-after",
            "0.05",
            "--dead-after",
            "1e18",
        ],
        capture_output=True,
        text=True,
        timeout=120,
    )
    out["monitor_kill_stalled"] = cp.returncode == obs_mon.EXIT_STALLED

    # C: resume B — must replay rounds k+1..end bit-identically
    _, out_c = launch(
        td,
        "C",
        ["--snapshot-dir", str(td / "snapB"), "--resume"],
    )
    res_c, timing_c = load(out_c)
    out["resume_round"] = timing_c.get("resume_round")
    out["kill_resume_identical"] = identical(res_c, ref)

    # D: worker 1 dies mid-save — shards staged, manifest never published
    rc_d, _ = launch(
        td,
        "D",
        [
            "--snapshot-dir",
            str(td / "snapD"),
            "--snapshot-every",
            "1",
            "--die-round",
            str(k),
            "--die-stage",
            "after-shards",
            "--die-process",
            "1",
        ],
        expect_fail=True,
    )
    out["torn_job_failed"] = rc_d != 0
    published_d = sorted(p.name for p in (td / "snapD").glob("step_*"))
    out["torn_last_published"] = (
        int(published_d[-1].split("_")[1]) if published_d else 0
    )

    # E: resume D — the torn round k is skipped, resume starts at k-1
    _, out_e = launch(
        td,
        "E",
        ["--snapshot-dir", str(td / "snapD"), "--resume"],
    )
    res_e, timing_e = load(out_e)
    out["torn_resume_round"] = timing_e.get("resume_round")
    out["torn_resume_identical"] = identical(res_e, ref)

    # F: single-process driver restores the N-process snapshots
    drv = PartitionDriver.resume(ef, CFG, td / "snapA")
    res_f = drv.run()
    out["crossproc_restore_identical"] = bool(
        (res_f.edge_part == ref.edge_part).all()
        and (res_f.vparts == ref.vparts).all()
    )

    # G: sharded finalize end to end with materialization FORBIDDEN —
    # the epilogue + cooperative artifact save must never touch the
    # O(m) global assignment, and the published artifact must be
    # byte-identical to a single-process save_artifact of the reference
    art_ref = td / "art_ref"
    save_artifact(
        art_ref,
        ref,
        ef.read_all(),
        int(ef.num_vertices),
        config_fingerprint=config_fingerprint(CFG),
        graph_fingerprint=graph_fingerprint(ef),
    )
    rc_g, _ = launch(
        td,
        "G",
        [
            "--snapshot-dir",
            str(td / "snapG"),
            "--artifact-out",
            str(td / "art_mh"),
        ],
        with_out=False,
        env_extra={"REPRO_FORBID_EDGE_PART_MATERIALIZE": "1"},
    )
    out["epilogue_no_gather"] = rc_g == 0
    out["artifact_bit_identical"] = dirs_identical(art_ref, td / "art_mh")

    # H: elastic process-count resume — B's snapshots (killed at k, PROCS
    # writers) restored by PROCS_ALT processes on the same 8 devices
    _, out_h = launch(
        td,
        "H",
        ["--snapshot-dir", str(td / "snapB"), "--resume"],
        procs=PROCS_ALT,
    )
    res_h, timing_h = load(out_h)
    out["elastic_resume_round"] = timing_h.get("resume_round")
    out["elastic_procs_identical"] = bool(
        identical(res_h, ref) and timing_h.get("resume_round") == k
    )

    # I: elastic device-count resume — A's fixed-point snapshots (8
    # shards) restored on a 4-device mesh; the store-backed reshard must
    # preserve every per-edge value, so the final result is identical
    _, out_i = launch(
        td,
        "I",
        ["--snapshot-dir", str(td / "snapA"), "--resume"],
        devices=4 // PROCS,
    )
    res_i, _timing_i = load(out_i)
    out["elastic_reshard_identical"] = bool(
        (res_i["edge_part"] == np.asarray(ref.edge_part)).all()
        and (res_i["vparts"] == np.asarray(ref.vparts)).all()
    )
    ef.close()

out["kill_resume_round_correct"] = (
    out["kill_last_published"] == k and out["resume_round"] == k
)
out["torn_round_skipped"] = (
    out["torn_last_published"] == k - 1 and out["torn_resume_round"] == k - 1
)

CHECKS = [
    "multihost_matches_spmd",
    "trace_per_host_logs",
    "trace_chrome_valid",
    "report_fields_ok",
    "stats_match",
    "monitor_hosts_ok",
    "monitor_rounds_monotone",
    "monitor_rf_matches_final",
    "monitor_live_exit",
    "monitor_kill_stalled",
    "kill_job_failed",
    "kill_resume_round_correct",
    "kill_resume_identical",
    "torn_job_failed",
    "torn_round_skipped",
    "torn_resume_identical",
    "crossproc_restore_identical",
    "epilogue_no_gather",
    "artifact_bit_identical",
    "elastic_procs_identical",
    "elastic_reshard_identical",
]
out["ok"] = all(out[c] for c in CHECKS)
print("RESULT " + json.dumps(out))
if not out["ok"]:
    failed = [c for c in CHECKS if not out[c]]
    print(f"FAILED checks: {failed}", file=sys.stderr)
    raise SystemExit(1)
