#!/usr/bin/env python3
"""Chip benchmark of Distributed NE: an EdgeFile to a published artifact.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``: its
configuration (``chipbench/configs/<config>.json``: graph, P, every
``NEConfig`` field, device count), its traffic (``chipbench/traffic/
<traffic>.json``) and each per-layer metric's reader (``chipbench/layers/
<metric>.py``).

Set-up makes the configuration's graph in numpy (``graphs.py``), writes it
as a canonical EdgeFile with the program's writer, and warms up every
program the window runs.  Traffic kinds:

* ``jobs`` — whole jobs back to back: the ``PartitionDriver`` constructor
  (ingest), ``step()`` until ``done``, ``finalize()``, ``save_artifact()``.
  Each job has its own ``NEConfig.seed`` from the traffic's ``job_seeds``,
  in an order drawn from ``--seed``; the window runs whole passes over
  them and ends with the first pass that ends at or after ``--seconds``,
  so every run does the same work.  ``partition_s`` is the window over its
  jobs.  Set-up runs one ingest and round per job seed, then a finalize
  and a save.
* ``rounds`` — rounds of one job whose ``NEConfig.seed`` is ``--seed``:
  set-up ingests and runs the warm-up rounds, the window steps rounds until
  ``--seconds`` have passed, and ``round_s`` is the window over its rounds.

With ``--trace 1`` the window is a profiler trace of ``traced_jobs`` jobs or
``traced_rounds`` rounds, and the metrics are the cell's per-layer ones.

After the window, once the peak device memory is read and the program's
state freed, the result is held to the plain reference (``reference.py``)
run on the same edges and seeds: every job's assignment,
replica map and counts, rf/eb/vb from the edges, and each artifact as
``load_artifact`` reads it back; in a rounds cell, the round state after
the window.  Each number compared is printed beside its limit, last on
stderr and last in the result line.  The last stdout line is the result
object.  Where JAX finds no TPU, or fewer chips than the cell asks for,
the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

# program settings the measured path must not pick up from the environment
PROGRAM_ENV = ("REPRO_TRACE", "REPRO_LIVE_METRICS", "REPRO_NE_KERNELS")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def log(*parts, **kv) -> None:
    print(" ".join([*map(str, parts),
                    *(f"{k}={v}" for k, v in kv.items())]),
          file=sys.stderr, flush=True)


class Spans:
    """Host spans: ``TraceAnnotation`` events for the profiler, and the
    same intervals on the host clock for the host-clock metrics."""

    def __init__(self, jax):
        self.jax = jax
        self.done: list = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        with self.jax.profiler.TraceAnnotation("chipbench." + name):
            yield
        self.done.append((name, t, time.perf_counter()))


def load_cell(root: Path, workload: str) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "chipbench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())

    def applies(metric) -> bool:
        return workload in metric.get("workloads", [workload])

    return dict(cell=cell, config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def reader(root: Path, metric: str):
    path = root / "chipbench" / "layers" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ne_fields(config: dict, seed: int) -> dict:
    return {k: (seed if k == "seed" and v == "run" else v)
            for k, v in config["ne"].items()}


def stitch(ep_sh, edges, num_devices: int):
    """Shard-order assignments back to edge order (shard ``d`` holds the
    edges the 2D hash sends to device ``d``, in edge order)."""
    import numpy as np

    from graphs import grid_device

    dev = grid_device(edges, num_devices)
    out = np.full(edges.shape[0], -1, np.int32)
    for d in range(num_devices):
        eids = np.flatnonzero(dev == d)
        out[eids] = ep_sh[d, : eids.size]
    return out


class Run:
    def __init__(self, root: Path, workload: str, seed: int, seconds: float,
                 trace: bool, require_tpu: bool = True):
        self.root, self.workload, self.seed = root, workload, seed
        self.seconds, self.trace = seconds, trace
        self.require_tpu = require_tpu
        self.spec = load_cell(root, workload)
        self.cell, self.config = self.spec["cell"], self.spec["config"]
        self.traffic = self.spec["traffic"]
        self.num_devices = self.config["num_devices"]
        self.work = root / ".chipbench" / workload
        self.peaks = json.loads((root / "chipbench" / "peaks.json")
                                .read_text())

    # -- set-up ----------------------------------------------------------
    def start_jax(self):
        for k in PROGRAM_ENV:
            os.environ.pop(k, None)
        cache = self.root / ".jax_cache"
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
        import jax

        jax.config.update("jax_compilation_cache_dir", str(cache))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        devs = jax.devices()
        self.jax, self.devs = jax, devs
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if self.require_tpu and devs[0].platform != "tpu":
            raise NoChip(f"JAX finds no TPU (platform {devs[0].platform})")
        if len(devs) < self.cell["chips"] or len(devs) < self.num_devices:
            raise NoChip(f"the cell asks for {self.cell['chips']} chips, "
                         f"JAX sees {len(devs)}")
        if self.device["kind"] not in self.peaks:
            raise KeyError(f"no peaks for device kind "
                           f"{self.device['kind']!r} in chipbench/peaks.json")
        self.peak = self.peaks[self.device["kind"]]
        self.spans = Spans(jax)
        log("device", platform=self.device["platform"],
            device_kind=repr(self.device["kind"]),
            devices=self.device["count"], workload=self.workload,
            seed=self.seed)

    def setup(self):
        import graphs
        import numpy as np
        from repro.core.partitioner import NEConfig

        shutil.rmtree(self.work, ignore_errors=True)
        self.work.mkdir(parents=True)
        t = time.perf_counter()
        self.edges, self.n = graphs.build(self.config)
        self.ef = graphs.write_edgefile(self.work / "graph.edges",
                                        self.edges, self.n)
        t_graph = time.perf_counter() - t
        if self.traffic["kind"] == "jobs":
            # the jobs and their seeds are the traffic's; the run's seed
            # draws their order
            seeds = self.traffic["job_seeds"]
            order = np.random.default_rng(self.seed).permutation(len(seeds))
            self.job_seeds = [seeds[i] for i in order]
            self.cfgs = {s: NEConfig(**ne_fields(self.config, s))
                         for s in seeds}
            # the seed is part of the round program's static config, so
            # each job's program is warmed up (a cache hit after the
            # first); one driver at a time, as in the window
            for i, s in enumerate(self.job_seeds):
                drv = self.driver(self.cfgs[s])
                drv.step()
                drv.done
                if i == len(seeds) - 1:
                    drv.finalize()
                    drv.save_artifact(self.work / "warmup")
                del drv
        else:
            self.cfg = NEConfig(**ne_fields(self.config, self.seed))
            self.drv = self.driver(self.cfg)
            for _ in range(self.traffic["warmup_rounds"]):
                self.drv.step()
            self.drv.done
        log("setup", n=self.n, m=self.edges.shape[0],
            graph_s=round(t_graph, 3),
            warmup_s=round(time.perf_counter() - t - t_graph, 3),
            setup_s=round(time.perf_counter() - T0, 3))

    def driver(self, cfg):
        from repro.runtime import PartitionDriver

        with self.spans("ingest"):
            return PartitionDriver(self.ef, cfg, num_devices=self.num_devices,
                                   mode=self.config["mode"])

    # -- the window --------------------------------------------------------
    def job(self, i: int, seed: int):
        cfg = self.cfgs[seed]
        drv = self.driver(cfg)
        steps = 0
        while True:
            with self.spans("done"):
                if drv.done:
                    break
            if steps > cfg.max_rounds:
                break       # a step that never advances: not a result
            with self.spans("step"):
                drv.step()
            steps += 1
        with self.spans("finalize"):
            res = drv.finalize()
        art = self.work / "jobs" / str(i)
        with self.spans("save"):
            drv.save_artifact(art)
        return seed, res, art

    def window(self):
        kind = self.traffic["kind"]
        if self.trace:
            self.trace_dir = self.root / ".chipbench" / "trace" / self.workload
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.jax.profiler.start_trace(str(self.trace_dir))
        w0 = time.perf_counter()
        self.setup_s = w0 - T0
        first_span = len(self.spans.done)
        self.jobs = []
        self.rounds = 0
        marks = [w0]
        if kind == "jobs":
            # whole passes over the traffic's jobs, until one ends at or
            # after --seconds: every run does the same work
            jobs = self.job_seeds
            if self.trace:
                jobs = jobs[: self.traffic["traced_jobs"]]
            while True:
                for s in jobs:
                    self.jobs.append(self.job(len(self.jobs), s))
                    marks.append(time.perf_counter())
                if self.trace or time.perf_counter() - w0 >= self.seconds:
                    break
        else:
            limit = self.traffic["traced_rounds"] if self.trace else None
            while True:
                with self.spans("done"):
                    if self.drv.done:
                        break
                with self.spans("step"):
                    self.drv.step()
                self.rounds += 1
                marks.append(time.perf_counter())
                if (self.rounds == limit if limit
                        else time.perf_counter() - w0 >= self.seconds):
                    break
        self.window_s = time.perf_counter() - w0
        self.window_spans = self.spans.done[first_span:]
        if self.trace:
            self.jax.profiler.stop_trace()
        self.attempted = len(self.jobs) if kind == "jobs" else self.rounds
        stats = [d.memory_stats() or {} for d in
                 self.devs[: self.num_devices]]
        self.memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        each = sorted(b - a for a, b in zip(marks, marks[1:]))
        log("window", window_s=round(self.window_s, 4),
            attempted=self.attempted, peak_hbm_bytes=self.memory_peak,
            each_min_s=round(each[0], 4) if each else None,
            each_median_s=round(each[len(each) // 2], 4) if each else None,
            each_max_s=round(each[-1], 4) if each else None,
            first_s=round(marks[1] - marks[0], 4) if each else None)

    # -- correctness ---------------------------------------------------------
    def program_state(self):
        """The round state after the window, on the host, then freed."""
        import numpy as np

        st = self.drv.state
        out = dict(edge_part=stitch(np.asarray(st.edge_part), self.edges,
                                    self.num_devices),
                   vparts=np.asarray(st.vparts),
                   degree_rest=np.asarray(st.degree_rest),
                   edges_per_part=np.asarray(st.edges_per_part),
                   remaining=int(st.remaining), rounds=int(st.rounds))
        del self.drv, st
        gc.collect()
        return out

    def compare(self) -> dict:
        """The numbers compared, each the worst over the window's answers."""
        import compare
        import reference
        from repro.runtime import load_artifact

        got = self.program_state() if not self.jobs else None
        gc.collect()
        t = time.perf_counter()
        if self.jobs:
            want = {}
            for s in dict.fromkeys(s for s, _, _ in self.jobs):
                ref = reference.Reference(self.edges, self.n,
                                          ne_fields(self.config, s),
                                          self.num_devices,
                                          mode=self.config["mode"]).run()
                want[s] = (ref, reference.stats(self.edges, ref.edge_part,
                                                self.n, self.config["ne"]
                                                ["num_partitions"]))
            answers = [compare.job_numbers(
                dict(edge_part=res.edge_part, vparts=res.vparts,
                     edges_per_part=res.edges_per_part, rounds=res.rounds,
                     rf=res.stats.replication_factor,
                     eb=res.stats.edge_balance,
                     vb=res.stats.vertex_balance),
                *want[s], load_artifact(art), self.edges)
                for s, res, art in self.jobs]
            rounds = [want[s][0].rounds for s in want]
            # the mean over whole passes of the same jobs
            self.rf = sum(res.stats.replication_factor
                          for _, res, _ in self.jobs) / len(self.jobs)
        else:
            ref = reference.Reference(self.edges, self.n,
                                      ne_fields(self.config, self.seed),
                                      self.num_devices,
                                      mode=self.config["mode"])
            want = ref.run(rounds=self.traffic["warmup_rounds"]
                           + self.rounds)
            rounds = [want.rounds]
            answers = [compare.state_numbers(got, want)]
        failed = sum(any(a.values()) for a in answers)
        # in a rounds cell the answer is the state: no round of it is right
        self.failed = self.attempted if failed and not self.jobs else failed
        log("reference", rounds=rounds, reference_s=round(
            time.perf_counter() - t, 3))
        return compare.worst(answers)

    # -- metrics -------------------------------------------------------------
    def end_to_end(self) -> dict:
        have = {"setup_s": self.setup_s,
                "peak_hbm_bytes": float(self.memory_peak)}
        if self.jobs:
            have["partition_s"] = self.window_s / len(self.jobs)
            have["replication_factor"] = self.rf
        elif self.rounds:
            have["round_s"] = self.window_s / self.rounds
        return {m["name"]: {"value": have[m["name"]], "unit": m["unit"]}
                for m in self.spec["end_to_end"]}

    def per_layer(self):
        import xplane

        tr = xplane.load(xplane.find(str(self.trace_dir)))
        ctx = dict(trace=tr, spans=self.window_spans,
                   kind=self.traffic["kind"], n=self.n,
                   m=int(self.edges.shape[0]),
                   p=self.config["ne"]["num_partitions"],
                   d=self.num_devices, peak=self.peak)
        metrics = {}
        for m in self.spec["per_layer"]:
            v = reader(self.root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        win = tr.span_window(*{s for s, _, _ in tr.spans})
        busy = tr.busy_ns(win) if win else 0.0
        device = {"busy_s": busy / 1e9,
                  "window_s": (win[1] - win[0]) / 1e9 if win else 0.0}
        breakdown = None
        if win:
            top = sorted(tr.op_ns(win).items(), key=lambda kv: -kv[1])[:10]
            gaps = sorted(tr.idle_gaps(win).items(),
                          key=lambda kv: -kv[1])[:10]
            breakdown = {"device_ops": [[k, v / 1e9] for k, v in top],
                         "idle_gaps": [[k, v / 1e9] for k, v in gaps]}
        return metrics, device, breakdown

    def result(self) -> dict:
        from compare import LIMITS, correct

        nums = self.compare()
        if self.trace:
            metrics, extra, breakdown = self.per_layer()
        else:
            metrics, extra, breakdown = self.end_to_end(), {}, None
        out = {"correct": correct(nums), "attempted": self.attempted,
               "failed": self.failed, "metrics": metrics,
               "device": dict(self.device,
                              memory_peak_bytes=self.memory_peak, **extra)}
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                         for k, v in nums.items()}
        for k, v in nums.items():
            log(f"check {k}={v} limit={LIMITS[k]}")
        return out

    def close(self):
        shutil.rmtree(self.work, ignore_errors=True)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True) -> dict:
    run = Run(root, workload, seed, seconds, trace, require_tpu)
    try:
        run.start_jax()
        run.setup()
        run.window()
        return run.result()
    finally:
        run.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"chipbench: {e}; this benchmark runs only on the chip")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
