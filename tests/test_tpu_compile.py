"""Compile-only checks against a described (unattached) TPU v5e.

The TPU compiler is installed with jaxlib, so the kernels and the SPMD
round step compile here for a ``v5e:2x2`` topology without a chip: what
the chip's compiler refuses (unlowerable primitives, unaligned slices,
VMEM overuse) fails here, at no chip time.  Nothing runs, so these say
nothing about results or speed.

The topology is described only inside the module-scoped fixture: one
process at a time may load the TPU library, and describing it while the
module is imported would make the test workers collect different tests.
Kernels are called directly with ``interpret=False`` — the ops front
door picks interpret mode from the (CPU) default backend.
"""
import contextlib
import functools
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.partitioner import NEConfig, alpha_limit
from repro.dist import compat
from repro.dist.partitioner_sm import (AXIS, EXCHANGE, STATE_SPECS, SpmdState,
                                       round_sync_payload_bytes,
                                       spmd_round_step, sync_chunk)
from repro.kernels.ne_round import ne_round as ne_pl
from repro.kernels.ne_round import ops as ne_ops

N_KERNEL = 1 << 22          # vertices at the one-chip Graph500 scale
P_NUM = 16                  # partitions of the chip smoke test
# round-step shapes, shrunk from scale 22 (N=2^22, M=6e7) so the file
# stays well under a minute of compile time on a CPU host
N_ROUND, CAP_ROUND = 1 << 16, 1 << 20
ROUNDS = [(1, False), (4, False), (4, True)]
ROUND_IDS = ["1-bool", "4-bool", "4-packed"]
COLLECTIVE = re.compile(r"(all-reduce|all-gather|collective-permute|"
                        r"reduce-scatter|all-to-all)(-start|-done)?")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _cache_off():
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def no_cache():
    with _cache_off():
        yield


def _partition_axis_scatters_gathers(text, p_num, chunk):
    """Scatters into a (P,) int32 target, and gathers that read the
    (chunk, P) int32 running count, in compiled HLO text: the round
    computes both as one-hot reductions, so neither may appear."""
    shapes = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", text))
    scatters = re.findall(rf"= s32\[{p_num}\]\S* scatter\(", text)
    gathers = [op for op in re.findall(r"= \S+ gather\(%([^,\s]+)", text)
               if shapes.get(op) == f"s32[{chunk},{p_num}]"]
    return scatters, gathers


def _state_scatters(text, n, p_num):
    """``(target shape, updates, op_name)`` of every scatter into the
    (N·P) replica map or an (N,) int32 vector such as D_rest, in compiled
    HLO text; op_name is '' where the instruction has none."""
    shapes = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", text))
    for line in text.splitlines():
        m = re.search(r"= (\w+)\[([\d,]*)\]\S* scatter\(%[^,\s]+, "
                      r"%[^,\s]+, %([^,\s)]+)\)", line)
        if not m:
            continue
        dtype, dims = m.group(1), [int(x) for x in m.group(2).split(",")]
        size = 1
        for x in dims:
            size *= x
        if (dtype, size) not in (("pred", n * p_num), ("s32", n)):
            continue
        updates = re.fullmatch(r"\w+\[(\d+)[\d,]*\]", shapes[m.group(3)])
        name = re.search(r'op_name="([^"]*)"', line)
        yield (f"{dtype}{dims}", int(updates.group(1)),
               name.group(1) if name else "")


def _collectives(text):
    """``(opcode, result shape, op_name)`` of every collective instruction
    in compiled HLO text; op_name is '' where the instruction has none."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*)$", line)
        if not m:
            continue
        rest, depth = m.group(1), 0
        for i, ch in enumerate(rest):   # the shape ends at a top-level space
            depth += (ch == "(") - (ch == ")")
            if ch == " " and depth == 0:
                break
        op = re.match(r"([a-z][a-z0-9-]*)\(", rest[i + 1:])
        if op and COLLECTIVE.fullmatch(op.group(1)):
            name = re.search(r'op_name="([^"]*)"', line)
            yield op.group(1), rest[:i], name.group(1) if name else ""


def _shape_bytes(shape: str) -> int:
    """Bytes of an HLO shape, a tuple's elements summed."""
    total = 0
    for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape):
        size = BYTES[dtype]
        for d in filter(None, dims.split(",")):
            size *= int(d)
        total += size
    return total


@pytest.fixture(scope="module")
def compiled_round(topo):
    """``(num_dev, use_pallas) -> (compiled, mesh, cfg)``: the round step
    at N_ROUND / CAP_ROUND, each compiled once for the module, with the
    persistent cache off and the packed round's Pallas kernels out of
    interpret mode (the ops front door would pick it from the CPU backend
    here)."""

    @functools.cache
    def get(num_dev, use_pallas):
        mesh = compat.make_mesh((num_dev,), (AXIS,),
                                devices=topo.devices[:num_dev])
        sharded = NamedSharding(mesh, P(AXIS, None))
        rep = NamedSharding(mesh, P())
        cfg = NEConfig(num_partitions=P_NUM, use_pallas=use_pallas)
        n, cap = N_ROUND, CAP_ROUND // num_dev
        limit = alpha_limit(cfg.alpha, CAP_ROUND, P_NUM)

        def spec(shape, dtype, sharding=rep):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        edges = spec((num_dev, cap), jnp.int32, sharded)
        vparts = (spec((n, ne_ops.replica_words(P_NUM)), jnp.uint32)
                  if use_pallas else spec((n, P_NUM), jnp.bool_))
        state = SpmdState(
            edge_part=edges, vparts=vparts,
            degree_rest=spec((n,), jnp.int32),
            edges_per_part=spec((P_NUM,), jnp.int32),
            key=spec((2,), jnp.uint32), rounds=spec((), jnp.int32),
            remaining=spec((), jnp.int32))
        with (_cache_off(), pytest.MonkeyPatch.context() as mp,
              jax.set_mesh(mesh)):
            mp.setattr(ne_ops, "_interpret", lambda: False)
            compiled = spmd_round_step.lower(
                cfg, limit, n, mesh, edges, edges,
                spec((num_dev, cap), jnp.bool_, sharded), state).compile()
        return compiled, mesh, cfg

    return get


def _kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled is not None
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("p", [P_NUM, 64, 96])
def test_pack_bits_compiles(topo, one_chip, no_cache, p):
    b = jax.ShapeDtypeStruct((N_KERNEL, p), jnp.bool_, sharding=one_chip)
    _kernel_compiles(lambda x: ne_pl.pack_bits(x, interpret=False), b)


@pytest.mark.parametrize("p", [P_NUM, 64, 96])
def test_unpack_bits_compiles(topo, one_chip, no_cache, p):
    w = jax.ShapeDtypeStruct((N_KERNEL, ne_ops.replica_words(p)),
                             jnp.uint32, sharding=one_chip)
    _kernel_compiles(
        lambda x: ne_pl.unpack_bits(x, p, interpret=False), w)


@pytest.mark.parametrize("p", [P_NUM, 64, 96])
def test_or_words_compiles(topo, one_chip, no_cache, p):
    w = jax.ShapeDtypeStruct((N_KERNEL, ne_ops.replica_words(p)),
                             jnp.uint32, sharding=one_chip)
    _kernel_compiles(lambda a, b: ne_pl.or_words(a, b, interpret=False),
                     w, w)


@pytest.mark.parametrize("num_dev,use_pallas", ROUNDS, ids=ROUND_IDS)
def test_spmd_round_step_compiles(compiled_round, num_dev, use_pallas):
    compiled, _, cfg = compiled_round(num_dev, use_pallas)
    assert compiled is not None
    text = compiled.as_text()
    if num_dev > 1:
        assert "all-reduce" in text or "collective-permute" in text
    if use_pallas:
        assert "tpu_custom_call" in text
    assert _partition_axis_scatters_gathers(
        text, P_NUM, min(cfg.edge_chunk, CAP_ROUND // num_dev)) == ([], [])
    mem = compiled.memory_analysis()
    if mem is not None:
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < 16 * 2**30


@pytest.mark.parametrize("num_dev,use_pallas", ROUNDS, ids=ROUND_IDS)
def test_sync_scatters_take_one_chunk(compiled_round, num_dev, use_pallas):
    """No scatter into the replica map or an (N,) vector spans the shard
    length C; the sync's (two replica-map sets and two D_rest adds in each
    of the round's two syncs) take ``sync_chunk(C)`` updates each."""
    compiled, _, _ = compiled_round(num_dev, use_pallas)
    c = CAP_ROUND // num_dev
    found = list(_state_scatters(compiled.as_text(), N_ROUND, P_NUM))
    assert all(updates < c for _, updates, _ in found), found
    sync = [(target, updates) for target, updates, name in found
            if "ne_select" not in name.split("/")]
    assert len(sync) == 8, found
    assert {updates for _, updates in sync} == {sync_chunk(c)}, found


@pytest.mark.parametrize("num_dev,use_pallas", ROUNDS, ids=ROUND_IDS)
def test_collectives_sit_in_the_exchange_scope(compiled_round, num_dev,
                                               use_pallas):
    """Every collective of the compiled round carries the ``ne_exchange``
    scope in its op_name, so a trace reads the exchange apart from the
    local work; on one device there is none."""
    compiled, _, _ = compiled_round(num_dev, use_pallas)
    found = list(_collectives(compiled.as_text()))
    assert bool(found) == (num_dev > 1)
    for opcode, _, name in found:
        assert EXCHANGE in name.split("/"), (opcode, name)


@pytest.mark.parametrize("num_dev,use_pallas", ROUNDS[1:], ids=ROUND_IDS[1:])
def test_collective_bytes_equal_the_payload_count(compiled_round, num_dev,
                                                  use_pallas):
    """The compiled collectives move what ``round_sync_payload_bytes``
    says: each counted once, by the data it completes with (an
    all-reduce's operand, a permute's, an all-gather's result)."""
    compiled, _, cfg = compiled_round(num_dev, use_pallas)
    sent = sum(_shape_bytes(shape)
               for opcode, shape, _ in _collectives(compiled.as_text())
               if not opcode.endswith("-start"))
    assert sent == round_sync_payload_bytes(cfg, N_ROUND, num_dev)


@pytest.mark.parametrize("num_dev,use_pallas", ROUNDS, ids=ROUND_IDS)
def test_round_returns_the_placed_layout(compiled_round, num_dev,
                                         use_pallas):
    """The round's outputs come back in ``place_state``'s layout, so the
    next round takes them as they are."""
    compiled, mesh, _ = compiled_round(num_dev, use_pallas)
    assert list(compiled.output_shardings) == [
        NamedSharding(mesh, spec) for spec in STATE_SPECS]
