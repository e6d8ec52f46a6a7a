"""The tracer's profiler sink: ``repro.obs`` spans and compile marks on the
JAX profiler's host plane, and the ``ne_*`` phase scopes in the round
program's HLO.

Everything runs on the CPU: a ``jax.profiler`` trace of the process there
has the host plane the annotations land on (device ops on the chip are
read by ``chipbench/program_trace.py``).
"""
from __future__ import annotations

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.graph import shard_edges
from repro.core.partitioner import NEConfig, alpha_limit
from repro.dist.partitioner_sm import (make_edge_mesh,
                                       round_sync_payload_bytes, shard_over,
                                       spmd_init_state, spmd_round_step,
                                       sync_chunk)
from repro.graphs.rmat import rmat
from repro.obs import trace as obs
from repro.runtime import PartitionDriver

PHASES = ("ne_select", "ne_one_hop", "ne_sync", "ne_two_hop")
# HLO opcodes that do a round's work, as against moving or reshaping it
WORK = {"gather", "scatter", "sort", "reduce", "all-reduce", "all-gather",
        "while"}


@pytest.fixture(autouse=True)
def _no_global_tracer():
    obs.disable()
    yield
    obs.disable()


def _repro_events(logdir) -> list[tuple]:
    """``(name, start_ns, end_ns, stats)`` of the host plane's ``repro.*``
    events, in start order."""
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(obs.ANNOTATION_PREFIX):
                        out.append((e.name[len(obs.ANNOTATION_PREFIX):],
                                    e.start_ns, e.end_ns, dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("jsonl", [False, True], ids=["no-tracer", "tracer"])
def test_span_reaches_the_profiler(tmp_path, jsonl):
    """With jax loaded, a front-door span is a ``repro.<name>`` host event
    whether or not a tracer is configured; JSONL only with one."""
    tr = obs.configure() if jsonl else None
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer", cat="test", k=1) as sp:
            sp.set(done=True)
            with obs.span("inner"):
                pass
    events = _repro_events(tmp_path)
    assert [e[0] for e in events] == ["outer", "inner"]
    # the span's own args ride on the profiler event; ``set`` reaches the
    # JSONL span alone
    assert events[0][3].get("k") == 1 and "done" not in events[0][3]
    if tr is None:
        assert obs.get_tracer() is None
    else:
        spans = [e for e in tr.events if e["ev"] == "span"]
        assert [s["name"] for s in spans] == ["inner", "outer"]
        assert spans[1]["args"] == {"k": 1, "done": True}


def test_traced_decorator_reaches_the_profiler(tmp_path):
    @obs.traced("work")
    def work(x):
        return x + 1

    with jax.profiler.trace(str(tmp_path)):
        assert work(1) == 2
    assert [e[0] for e in _repro_events(tmp_path)] == ["work"]


def test_compile_marks(tmp_path):
    """A new shape inside a trace gives one backend-compile mark, tagged
    with its event and seconds; a cached call gives none."""
    obs.watch_compiles()
    obs.watch_compiles()                  # once per process, however called
    f = jax.jit(lambda a: a * 3 + 1)
    five, seven = jnp.ones(5), jnp.ones(7)
    f(five).block_until_ready()
    tr = obs.configure()
    with jax.profiler.trace(str(tmp_path)):
        f(five).block_until_ready()
        with obs.span("cut"):
            pass
        f(seven).block_until_ready()
    events = _repro_events(tmp_path)
    cut = next(e[1] for e in events if e[0] == "cut")
    marks = [e for e in events if e[0] == "compile"]
    assert marks and all(e[1] > cut for e in marks)
    assert all(e[3]["event"] in obs.COMPILE_EVENTS and e[3]["seconds"] > 0
               for e in marks)
    backend = [e for e in marks if e[3]["event"]
               == "/jax/core/compile/backend_compile_duration"]
    assert len(backend) == 1
    totals = [e["value"] for e in tr.events
              if e["ev"] == "counter" and e["name"] == "compiles"]
    assert totals[-1] == len(marks)


def test_driver_spans_on_the_profiler(tmp_path):
    """Each driver call's spans, nested as the round's host time is read:
    ``round`` holds ``round_dispatch``, ``round_wait`` and ``round_read``;
    ``done`` reads once per fresh state."""
    g = rmat(8, 8, seed=1)
    cfg = NEConfig(num_partitions=4, k_sel=16, edge_chunk=1 << 10)
    with jax.profiler.trace(str(tmp_path / "trace")):
        drv = PartitionDriver(g, cfg, num_devices=1)
        while not drv.done:
            drv.step()
        drv.save_artifact(tmp_path / "art")
    events = _repro_events(tmp_path / "trace")
    count = {}
    for name, *_ in events:
        count[name] = count.get(name, 0) + 1
    rounds = drv.rounds
    assert rounds > 1
    for name in ("round", "round_dispatch", "round_wait", "round_read"):
        assert count[name] == rounds, name
    # each SPMD round says on the trace what its sync sends
    want = round_sync_payload_bytes(drv.cfg, drv.n, 1)
    assert [e[3].get("sync_payload_bytes") for e in events
            if e[0] == "round"] == [want] * rounds
    # and the chunk its fold scatters in, with the new edges the round
    # before it folded (the last round's the driver holds after the loop)
    assert [e[3].get("sync_chunk") for e in events if e[0] == "round"] \
        == [sync_chunk(drv._mask_sh.shape[1])] * rounds
    new = [e[3].get("sync_new_edges") for e in events if e[0] == "round"]
    assert new[0] is None and min(new[1:]) >= 0
    assert sum(new[1:]) + drv._new_edges == drv.m
    assert count["done_read"] == rounds + 1
    for name in ("ingest", "ingest_shards", "ingest_place", "finalize",
                 "device_get", "stitch_edge_part", "finalize_result",
                 "save_artifact"):
        assert count[name] == 1, name
    spans = {n: [(a, b) for m, a, b, _ in events if m == n]
             for n in count}
    for child in ("round_dispatch", "round_wait", "round_read"):
        for a, b in spans[child]:
            assert any(a0 <= a and b <= b0 for a0, b0 in spans["round"])
    for child in ("device_get", "stitch_edge_part", "finalize_result"):
        (a0, b0), (a, b) = spans["finalize"][0], spans[child][0]
        assert a0 <= a and b <= b0


def _instructions(hlo: str):
    """``(opcode, op_name)`` of every instruction of an HLO module's text,
    nested computations included; op_name is '' where the compiler made
    the instruction itself."""
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*)$", line)
        if not m:
            continue
        rest = m.group(1)
        if rest.startswith("("):           # a tuple shape: skip to its end
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            rest = rest[i + 1:]
        else:
            rest = rest.partition(" ")[2]
        op = re.match(r"\s*([a-z][a-z0-9-]*)\(", rest)
        name = re.search(r'op_name="([^"]*)"', line)
        if op:
            yield op.group(1), name.group(1) if name else ""


def _round_hlo(path: str) -> str:
    g = rmat(10, 16, seed=0)
    cfg = NEConfig(num_partitions=16, k_sel=64, edge_chunk=1 << 12,
                   use_pallas=path == "spmd-packed").clamped(g.num_vertices)
    limit = alpha_limit(cfg.alpha, g.num_edges, cfg.num_partitions)
    shards, masks, _, _ = shard_edges(np.asarray(g.edges), 1)
    mesh = make_edge_mesh(1)
    state = spmd_init_state(shards, masks, g.num_vertices, cfg, mesh)
    with jax.set_mesh(mesh):
        return spmd_round_step.lower(
            cfg, limit, g.num_vertices, mesh,
            shard_over(mesh, shards[:, :, 0]),
            shard_over(mesh, shards[:, :, 1]), shard_over(mesh, masks),
            state).compile().as_text()


@pytest.mark.parametrize("path", ["spmd-bool", "spmd-packed"])
def test_round_work_sits_under_a_phase_scope(path):
    """Graph500 scale 10 on the CPU: every instruction of the compiled
    round that does work and comes from a JAX op names one of the four
    phases in its op_name; each phase has such work."""
    seen = set()
    for opcode, name in _instructions(_round_hlo(path)):
        if opcode not in WORK or not name:
            continue
        scopes = [c for c in name.split("/") if c in PHASES]
        assert scopes, (opcode, name)
        seen.add(scopes[-1])
    assert seen == set(PHASES)
