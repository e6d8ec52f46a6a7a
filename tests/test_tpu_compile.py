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
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.partitioner import NEConfig, alpha_limit
from repro.dist import compat
from repro.dist.partitioner_sm import AXIS, SpmdState, spmd_round_step
from repro.kernels.ne_round import ne_round as ne_pl
from repro.kernels.ne_round import ops as ne_ops

N_KERNEL = 1 << 22          # vertices at the one-chip Graph500 scale
P_NUM = 16                  # partitions of the chip smoke test
# round-step shapes, shrunk from scale 22 (N=2^22, M=6e7) so the file
# stays well under a minute of compile time on a CPU host
N_ROUND, CAP_ROUND = 1 << 16, 1 << 20


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


@pytest.fixture
def no_cache():
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _partition_axis_scatters_gathers(text, p_num, chunk):
    """Scatters into a (P,) int32 target, and gathers that read the
    (chunk, P) int32 running count, in compiled HLO text: the round
    computes both as one-hot reductions, so neither may appear."""
    shapes = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", text))
    scatters = re.findall(rf"= s32\[{p_num}\]\S* scatter\(", text)
    gathers = [op for op in re.findall(r"= \S+ gather\(%([^,\s]+)", text)
               if shapes.get(op) == f"s32[{chunk},{p_num}]"]
    return scatters, gathers


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


@pytest.mark.parametrize("num_dev,use_pallas",
                         [(1, False), (4, False), (4, True)],
                         ids=["1-bool", "4-bool", "4-packed"])
def test_spmd_round_step_compiles(topo, no_cache, monkeypatch, num_dev,
                                  use_pallas):
    # the packed round reaches the Pallas kernels through the ops front
    # door, which would pick interpret mode from the CPU backend here
    monkeypatch.setattr(ne_ops, "_interpret", lambda: False)
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
        edge_part=edges, vparts=vparts, degree_rest=spec((n,), jnp.int32),
        edges_per_part=spec((P_NUM,), jnp.int32),
        key=spec((2,), jnp.uint32), rounds=spec((), jnp.int32),
        remaining=spec((), jnp.int32))
    compiled = spmd_round_step.lower(
        cfg, limit, n, mesh, edges, edges,
        spec((num_dev, cap), jnp.bool_, sharded), state).compile()
    assert compiled is not None
    text = compiled.as_text()
    if num_dev > 1:
        assert "all-reduce" in text or "collective-permute" in text
    if use_pallas:
        assert "tpu_custom_call" in text
    assert _partition_axis_scatters_gathers(
        text, P_NUM, min(cfg.edge_chunk, cap)) == ([], [])
    mem = compiled.memory_analysis()
    if mem is not None:
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < 16 * 2**30
