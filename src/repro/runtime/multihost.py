"""True multi-controller SPMD partitioning under ``jax.distributed``.

PR 3 left every "jax.distributed-aware" surface running single-process:
the ingestion plan, the sharded snapshot files and the round state machine
all *spoke* multi-host but executed in one interpreter with 8 forced host
devices.  This module is the process-orchestration layer that closes that
gap — the repo's realization of the paper's deployment model (§7: one
allocation process per machine, rounds separated by real collectives):

* **worker side** — :func:`initialize_distributed` brings up the
  distributed runtime (coordinator address, process id/count, gloo CPU
  collectives), :func:`worker_main` drives one process's share of a run:
  ingest only this host's block range through the
  :mod:`repro.runtime.cluster` exchange, build the *global* mesh via
  :func:`repro.dist.partitioner_sm.make_edge_mesh`, step
  ``spmd_round_step`` through :class:`repro.runtime.driver.PartitionDriver`
  with per-host snapshot writes, and publish the finalized result from
  process 0;

* **array plumbing** — :func:`global_shard_array` / :func:`replicate`
  assemble ``jax.Array``\\ s spanning all processes from the slices each
  process owns (``jax.make_array_from_single_device_arrays``), and
  :func:`gather_to_host` is the one deliberate all-gather — since the
  sharded finalize it backs only the *lazy*
  ``PartitionResult.edge_part`` materialization (tests, the ``--out``
  npz dump); the epilogue itself finalizes per owned slice and the
  artifact persists through the cooperative multi-writer save;

* **launcher side** — :func:`launch_local` spawns N local worker
  processes with their own device counts (the honest local stand-in for N
  machines), monitors them, and kills the survivors as soon as any worker
  dies — the cluster-manager behavior the kill-at-round-k/resume tests
  rely on.  ``scripts/launch_multihost.py`` is the CLI over both sides.

Bit-identity contract: a 2-process × 4-device run produces the same edge
assignment, replica sets and round count as the single-process 8-device
``partition_spmd`` on the same canonical EdgeFile, because the mesh, the
shard layout, the replicated PRNG key and every collective are identical —
asserted by ``tests/spmd/run_multihost_checks.py``.
"""
from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist import compat
from repro.dist.partitioner_sm import SpmdState, empty_vparts

EXIT_FAULT = 17  # what an injected crash (test hook) exits with


def initialize_distributed(
    coordinator: str,
    num_processes: int,
    process_id: int,
) -> None:
    """Bring up ``jax.distributed`` for this worker.

    Must run before anything queries devices.  On the CPU backend,
    cross-process collectives need the gloo implementation; accelerator
    backends ignore the knob and use their own.
    """
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


# ---------------------------------------------------------------------------
# global-array plumbing
# ---------------------------------------------------------------------------


def mesh_devices(mesh) -> list:
    """The mesh's devices in global shard order (flat leading axis)."""
    return list(np.asarray(mesh.devices).flat)


def owned_indices(mesh) -> list[int]:
    """Global shard indices whose device lives in this process."""
    pid = compat.process_env()[0]
    return [
        i
        for i, dev in enumerate(mesh_devices(mesh))
        if dev.process_index == pid
    ]


def global_shard_array(mesh, per_index: dict, shape_tail: tuple, dtype):
    """A (D, *tail) ``jax.Array`` sharded over the mesh's leading axis,
    assembled from the slices this process owns.

    ``per_index[i]`` is the (*tail,) slice for global shard index ``i`` —
    exactly the indices of :func:`owned_indices`.  Every process calls this
    with *its* slices and gets the same logical global array.
    """
    devs = mesh_devices(mesh)
    axis = mesh.axis_names[0]
    sharding = NamedSharding(mesh, P(axis, *(None,) * len(shape_tail)))
    arrs = [
        jax.device_put(np.asarray(per_index[i], dtype)[None], devs[i])
        for i in sorted(per_index)
    ]
    shape = (len(devs), *shape_tail)
    return jax.make_array_from_single_device_arrays(shape, sharding, arrs)


def replicate(mesh, x):
    """A fully-replicated global ``jax.Array`` from a host value.

    The value must be identical on every process (all replicated round
    state is — it is derived deterministically from the shared plan).
    Built from one copy per local device of the mesh.
    """
    x = np.asarray(x)
    pid = compat.process_env()[0]
    local = [d for d in mesh_devices(mesh) if d.process_index == pid]
    arrs = [jax.device_put(x, d) for d in local]
    return jax.make_array_from_single_device_arrays(
        x.shape, NamedSharding(mesh, P()), arrs
    )


def _identity(x):
    return x


def gather_to_host(mesh, arr) -> np.ndarray:
    """All-gather a device-sharded global array back to host numpy.

    The one deliberate O(global) transfer, and a *collective* — every
    process must call it together.  Since the sharded finalize only the
    lazy ``PartitionResult.edge_part`` materialization uses it; the
    epilogue proper never does (the CI ``finalize-mem`` gate and the
    ``REPRO_FORBID_EDGE_PART_MATERIALIZE`` integration check hold it to
    that).
    """
    out = jax.jit(_identity, out_shardings=NamedSharding(mesh, P()))(arr)
    jax.block_until_ready(out)
    return np.asarray(out)


def spmd_init_state_global(
    mesh,
    cap: int,
    n: int,
    cfg,
    degree: np.ndarray,
    m_total: int,
    owned: list[int],
) -> SpmdState:
    """Multi-process twin of ``spmd_init_state``: identical field values,
    but ``edge_part`` is assembled from per-owned-device slices and every
    replicated field is an explicit fully-replicated global array."""
    p_num = cfg.num_partitions
    edge_part = global_shard_array(
        mesh,
        {i: np.full((cap,), -1, np.int32) for i in owned},
        (cap,),
        np.int32,
    )
    return SpmdState(
        edge_part=edge_part,
        vparts=replicate(mesh, empty_vparts(n, cfg)),
        degree_rest=replicate(mesh, degree.astype(np.int32)),
        edges_per_part=replicate(mesh, np.zeros((p_num,), np.int32)),
        key=replicate(mesh, np.asarray(jax.random.PRNGKey(cfg.seed))),
        rounds=replicate(mesh, np.zeros((), np.int32)),
        remaining=replicate(mesh, np.asarray(m_total, np.int32)),
    )


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------


def worker_main(ns) -> int:
    """One process's share of a multi-controller partitioning run.

    ``ns`` is the parsed CLI namespace of ``scripts/launch_multihost.py``
    (see there for the flag reference).  Flow: distributed init → driver
    construction (per-host ingestion + global mesh) or barrier'd resume →
    round stepping with per-host snapshot writes → finalize → process 0
    publishes ``result.npz`` + ``timing.json`` under ``--out``.
    """
    from repro import compile_cache

    compile_cache.enable()
    initialize_distributed(ns.coordinator, ns.num_processes, ns.process_id)
    from repro.core.partitioner import NEConfig
    from repro.io.edgefile import EdgeFile
    from repro.obs import live
    from repro.obs import report as obs_report
    from repro.obs import trace as obs
    from repro.runtime.driver import PartitionDriver

    pid = jax.process_index()
    hyper = dict(
        num_partitions=ns.partitions,
        alpha=ns.alpha,
        lam=ns.lam,
        k_sel=ns.k_sel,
        edge_chunk=ns.edge_chunk,
        max_rounds=ns.max_rounds,
        seed=ns.seed,
    )
    partitioner = getattr(ns, "partitioner", "ne")
    if partitioner == "hybrid":
        from repro.core.hybrid import HybridConfig

        cfg = HybridConfig(budget_frac=ns.budget_frac, **hyper)
        driver_mode = "hybrid"
    else:
        cfg = NEConfig(**hyper)
        driver_mode = "spmd"
    # one tracer per worker, always on: it is the single source of every
    # published timing (perf_counter span durations — monotonic,
    # NTP-immune; the meta line's start_unix is the only epoch stamp).
    # With a trace dir it also streams the per-host JSONL log; without
    # one the events stay in memory and only back timing.json.
    trace_dir = getattr(ns, "trace_dir", None)
    env_trace = os.environ.get("REPRO_TRACE", "")
    if trace_dir is None and env_trace not in ("", "0"):
        trace_dir = (
            env_trace
            if env_trace != "1"
            else (os.path.join(ns.out, "trace") if ns.out else None)
        )
    log_path = (
        os.path.join(trace_dir, obs.log_name(pid)) if trace_dir else None
    )
    tracer = obs.configure(
        path=log_path,
        process=pid,
        meta={
            "process_id": pid,
            "num_processes": int(jax.process_count()),
            "devices": int(jax.device_count()),
        },
    )
    # live metrics bus (repro.obs.live): each worker publishes its own
    # heartbeat/quality stream to the shared metrics dir; never a
    # collective, so enabling it on all workers uniformly (launcher flag
    # or env, both gang-wide) keeps the run bit-identical to unmonitored.
    metrics_dir = getattr(ns, "metrics_dir", None)
    env_live = os.environ.get("REPRO_LIVE_METRICS", "")
    if metrics_dir is None and env_live not in ("", "0"):
        metrics_dir = (
            env_live
            if env_live != "1"
            else (os.path.join(ns.out, live.BUS_DIRNAME) if ns.out else None)
        )
    if metrics_dir is not None:
        manifest = None
        if pid == 0:  # one atomic run.json, from the lowest-rank worker
            manifest = {
                "num_processes": int(jax.process_count()),
                "devices": int(jax.device_count()),
                "partitions": ns.partitions,
                "edgefile": os.fspath(ns.edgefile),
            }
        live.configure(
            metrics_dir,
            process=pid,
            meta={
                "process_id": pid,
                "num_processes": int(jax.process_count()),
            },
            manifest=manifest,
        )
    extra: dict = {}
    with EdgeFile(ns.edgefile) as ef:
        kwargs = dict(
            mode=driver_mode,
            snapshot_every=ns.snapshot_every,
            keep=ns.keep,
            exchange_dir=ns.exchange_dir,
        )
        if ns.resume:
            drv = PartitionDriver.resume(ef, cfg, ns.snapshot_dir, **kwargs)
            extra["resume_round"] = drv.rounds
        else:
            drv = PartitionDriver(
                ef, cfg, snapshot_dir=ns.snapshot_dir, **kwargs
            )
        if (
            ns.die_round >= 0
            and pid == ns.die_process
            and ns.die_stage in ("after-shards", "after-publish")
        ):

            def fault_hook(stage, round_k):
                if stage == ns.die_stage and round_k >= ns.die_round:
                    os._exit(EXIT_FAULT)

            drv.snapshot_fault_hook = fault_hook
        while not drv.done:
            drv.step()  # records the per-round span + gauges
            if (
                ns.die_round >= 0
                and pid == ns.die_process
                and ns.die_stage == "after-round"
                and drv.rounds >= ns.die_round
            ):
                os._exit(EXIT_FAULT)
        res = drv.finalize()
        extra["rounds"] = int(res.rounds)
        extra["platform"] = jax.devices()[0].platform
        extra["device_kind"] = jax.devices()[0].device_kind
        if res.stats is not None:
            # quality metrics from the sharded epilogue's (P,)-sized
            # partials — computed without the global assignment
            extra["replication_factor"] = res.stats.replication_factor
            extra["edge_balance"] = res.stats.edge_balance
            extra["vertex_balance"] = res.stats.vertex_balance
        if drv.snapshot is not None:
            extra["snapshot_rounds"] = drv.snapshot.rounds()
        if getattr(ns, "artifact_out", None):
            # cooperative multi-writer save: every process participates,
            # nobody materializes edge_part
            with obs.span("artifact_save", cat="runtime"):
                drv.save_artifact(ns.artifact_out)
        if ns.out:
            # materializing the lazy edge_part runs the one deliberate
            # all-gather — a collective, so EVERY process forces it, not
            # just the writer (this dump is the test/debug surface; the
            # production output is --artifact-out)
            with obs.span("gather_result", cat="runtime"):
                edge_part = res.edge_part
            if pid == 0:
                outd = Path(ns.out)
                outd.mkdir(parents=True, exist_ok=True)
                np.savez(
                    outd / "result.npz",
                    edge_part=edge_part,
                    vparts=res.vparts,
                    edges_per_part=res.edges_per_part,
                    rounds=res.rounds,
                    leftover=res.leftover,
                )
                timing = obs_report.legacy_timing(tracer, extra)
                (outd / "timing.json").write_text(json.dumps(timing))
    tracer.close()  # flush this host's JSONL log (final RSS sample)
    live.disable()  # close this worker's metrics stream (no-op when off)
    compat.barrier("run-done")
    return 0


# ---------------------------------------------------------------------------
# launcher side (local stand-in for a cluster manager)
# ---------------------------------------------------------------------------

_FORCE_DEVICES = re.compile(r"--xla_force_host_platform_device_count=\d+\s*")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(devices_per_process: int, extra: dict | None = None) -> dict:
    """Worker environment: force the per-process host device count
    (replacing any inherited forcing, e.g. CI's 8-device tier-1 env) and
    make ``repro`` importable.  The platform is inherited, never
    defaulted: a worker on a chip host partitions on the chip."""
    env = dict(os.environ)
    flags = _FORCE_DEVICES.sub("", env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_process} "
        + flags
    ).strip()
    src = str(Path(__file__).resolve().parents[2])
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    if extra:
        env.update(extra)
    return env


def launch_local(
    worker_argv: list[str],
    num_processes: int,
    devices_per_process: int,
    coordinator: str | None = None,
    log_dir: str | os.PathLike | None = None,
    timeout: float = 1800.0,
    grace: float = 10.0,
) -> tuple[int, list[str]]:
    """Spawn ``num_processes`` local workers and babysit them.

    ``worker_argv`` is the command prefix (e.g. ``[python, script, *job
    flags]``); per-process ``--worker --process-id i --num-processes N
    --coordinator addr`` flags are appended.  Monitoring implements the
    cluster-manager contract the failure tests rely on: the first worker
    to exit nonzero (or a deadline overrun) gets the whole gang torn down
    — SIGTERM, then SIGKILL after ``grace`` — because a surviving peer is
    blocked in a collective whose counterpart died.  Returns the overall
    exit code (first nonzero, 0 if all clean) and each worker's log.

    One process per chip: a worker that reaches an accelerator takes
    every local chip, so several local workers run only pinned to the
    CPU (``JAX_PLATFORMS=cpu``, the local stand-in for N machines); on a
    chip host, ``num_processes=1`` runs one worker that owns every chip.
    """
    env = child_env(devices_per_process)
    if num_processes > 1 and env.get("JAX_PLATFORMS", "") != "cpu":
        raise ValueError(
            f"{num_processes} local workers would share this host's "
            "chips: run one process (it owns every local chip), or pin "
            "the gang to the CPU with JAX_PLATFORMS=cpu"
        )
    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    # worker output always goes to files, never PIPE: the monitor loop
    # below doesn't drain pipes, and a worker that filled the OS pipe
    # buffer (verbose gloo/XLA logging) would block forever
    if log_dir is None:
        log_dir = tempfile.mkdtemp(prefix="multihost_logs_")
    log_dir = Path(log_dir)
    log_dir.mkdir(parents=True, exist_ok=True)
    procs, logs = [], []
    for i in range(num_processes):
        cmd = worker_argv + [
            "--worker",
            "--process-id",
            str(i),
            "--num-processes",
            str(num_processes),
            "--coordinator",
            coordinator,
        ]
        log = open(log_dir / f"proc{i:03d}.log", "w")
        procs.append(
            subprocess.Popen(
                cmd,
                stdout=log,
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
        )
        logs.append(log)
    deadline = time.time() + timeout
    first_fault = None  # exit code of the first worker that died on its own
    while True:
        codes = [p.poll() for p in procs]
        if all(c is not None for c in codes):
            break
        fault = next((c for c in codes if c not in (None, 0)), None)
        if fault is not None:
            first_fault = fault
            break
        if time.time() > deadline:
            first_fault = 124  # the conventional timeout exit code
            break
        time.sleep(0.1)
    if first_fault is not None:
        # survivors are blocked in collectives whose peer died; SIGTERM is
        # usually ignored inside gloo, so escalate to SIGKILL after grace
        for p in procs:
            if p.poll() is None:
                p.terminate()
        t0 = time.time()
        while (
            any(p.poll() is None for p in procs)
            and time.time() - t0 < grace
        ):
            time.sleep(0.1)
        for p in procs:
            if p.poll() is None:
                p.kill()
    outputs = []
    for p, log in zip(procs, logs):
        p.wait()
        log.close()
        outputs.append(Path(log.name).read_text())
    if first_fault is not None:
        rc = first_fault
    else:
        rc = next((p.returncode for p in procs if p.returncode != 0), 0)
    return rc, outputs


__all__ = [
    "EXIT_FAULT",
    "child_env",
    "free_port",
    "gather_to_host",
    "global_shard_array",
    "initialize_distributed",
    "launch_local",
    "mesh_devices",
    "owned_indices",
    "replicate",
    "spmd_init_state_global",
    "worker_main",
]
