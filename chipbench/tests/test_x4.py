"""The four-chip cell ``graph500_s20.x4.rounds``: its configuration, the
exchange readers of ``exchange.py``, and the reference against the program
on four CPU devices."""
import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import exchange
import graphs
import minibench
import program_trace
import reference
import run
import xplane

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
CONF = json.loads((HERE / "configs" / "graph500_s20_p16_x4.json").read_text())
ONE = json.loads((HERE / "configs" / "graph500_s20_p16.json").read_text())
SCOPED = str(HERE / "testdata" / "rounds_s12_scoped.xplane.pb")
CELL = "graph500_s20.x4.rounds"
EXCHANGE = ("round_exchange_ms", "round_exchange_roofline",
            "round_exchange_mb")
PEAK = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]


def read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_config_is_the_one_chip_config_on_four():
    from repro.core.partitioner import NEConfig

    assert CONF["num_devices"] == 4
    assert set(CONF["ne"]) == {f.name for f in dataclasses.fields(NEConfig)}
    for key in ("graph", "ne", "mode"):
        assert CONF[key] == ONE[key], key
    assert CONF["guarantees"][:-1] == ONE["guarantees"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONF["name"], "rounds", 4)
    x4 = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert x4 == {f"{name}.x4" for name in EXCHANGE + (
        "round_device_ms", "round_idle", "round_hbm_roofline",
        "round_select_ms", "round_one_hop_ms", "round_sync_ms",
        "round_two_hop_ms", "round_host_ms")}


def test_exchange_min_bytes():
    assert exchange.exchange_min_bytes(1_048_576, 16, 4) == 18_874_752
    assert exchange.exchange_min_bytes(1_048_576, 16, 1) == 0


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(program_trace, "TRACES",
                        str(HERE / "testdata" / "*.xplane.pb"))


def test_readers_read_nothing_on_one_chip(recorded):
    """A one-chip trace has no collective and no payload argument: the
    exchange readers give nothing, and the copies read as the one-chip
    cell's readers do."""
    ctx = dict(trace=xplane.load(SCOPED), spans=[], kind="rounds",
               n=1 << 12, m=48_655, p=16, d=1, peak=PEAK)
    for name in EXCHANGE:
        assert read(f"{name}.x4", ctx) is None
    assert exchange.exchange_ms(ctx) is None
    for name in ("round_device_ms", "round_sync_ms", "round_host_ms"):
        assert read(f"{name}.x4", ctx) == read(f"{name}.rounds", ctx)


def synthetic(payload=142_606_720):
    """Two traced rounds on two devices: each round 10 ms of ``ne_sync``
    work, of which a 2 ms (device 0) or 4 ms (device 1) all-reduce under
    ``ne_exchange``, and 6 ms of ``ne_two_hop`` whose 1 ms gather sits
    under ``ne_exchange``; the ``round`` spans carry ``payload``."""
    ms = 1_000_000
    base = "jit(spmd_round_step)/shard_map"
    devices, xdevices = [], []
    for dev in range(2):
        ops = []
        for r in range(2):
            t = r * 100 * ms
            ar = (2 + 2 * dev) * ms
            ops += [("fusion.1", t, t + 10 * ms - ar, f"{base}/ne_sync/add"),
                    ("all-reduce.1", t + 10 * ms - ar, t + 10 * ms,
                     f"{base}/ne_sync/ne_exchange/psum"),
                    ("fusion.2", t + 10 * ms, t + 15 * ms,
                     f"{base}/ne_two_hop/while"),
                    ("psum.2", t + 15 * ms, t + 16 * ms,
                     f"{base}/ne_two_hop/ne_exchange/psum")]
        devices.append(ops)
        xdevices.append([op[:3] for op in ops])
    spans = []
    for r in range(2):
        t = r * 100 * ms
        spans += [("done", t - ms, t), ("step", t, t + 20 * ms)]
    rounds = [("round", t, t + 20 * ms, {} if payload is None
               else {"sync_payload_bytes": payload})
              for t in (0, 100 * ms)]
    ctx = dict(trace=xplane.Trace(xdevices, spans), spans=[], kind="rounds",
               n=1 << 20, m=15_701_786, p=16, d=4, peak=PEAK)
    return ctx, program_trace.ProgramTrace(devices, rounds)


@pytest.mark.parametrize("payload", [142_606_720, None])
def test_readers_on_a_synthetic_exchange(monkeypatch, payload):
    ctx, pt = synthetic(payload)
    monkeypatch.setattr(program_trace, "_program_trace", lambda ctx: pt)
    ms = read("round_exchange_ms.x4", ctx)
    assert ms == pytest.approx((2 + 4) / 2 + 1)
    # the exchange is still part of the phase it sits in
    assert read("round_sync_ms.x4", ctx) == pytest.approx(10)
    assert read("round_two_hop_ms.x4", ctx) == pytest.approx(6)
    least_s = 18_874_752 / 200e9
    assert read("round_exchange_roofline.x4", ctx) == pytest.approx(
        100 * least_s / 4e-3)
    mb = read("round_exchange_mb.x4", ctx)
    assert mb == (None if payload is None else pytest.approx(142.60672))


def test_reference_equals_program_on_four_devices(tmp_path):
    """The configuration's NE fields at scale 12, four CPU devices: the
    program's round state equals the reference's after each of three
    rounds, and at the end."""
    from repro.core.partitioner import NEConfig
    from repro.runtime import PartitionDriver

    conf = minibench.small_config(CONF["name"], scale=12)
    edges, n = graphs.build(conf)
    ne = run.ne_fields(conf, 2**31 + 11)
    ef = graphs.write_edgefile(tmp_path / "g.edges", edges, n)
    drv = PartitionDriver(ef, NEConfig(**ne), num_devices=conf["num_devices"],
                          mode=conf["mode"])
    ref = reference.Reference(edges, n, ne, conf["num_devices"],
                              mode=conf["mode"])
    st = ref.init()
    for _ in range(3):
        drv.step()
        ref.step(st)
        np.testing.assert_array_equal(np.asarray(drv.state.vparts),
                                      st.vparts)
        np.testing.assert_array_equal(np.asarray(drv.state.degree_rest),
                                      st.degree_rest)
    res = drv.run()
    while not ref.done(st):
        ref.step(st)
    assert res.rounds == st.rounds and res.leftover == 0
    np.testing.assert_array_equal(res.edge_part, st.edge_part)
    np.testing.assert_array_equal(res.edges_per_part, st.edges_per_part)


def test_traced_run_on_the_cpu_raises_nothing(tmp_path):
    """The cell's traced run at a test size on four CPU devices: correct,
    and with no TPU plane in the trace no device reader reports."""
    import jax

    conf = minibench.small_config(CONF["name"], scale=9)
    root = minibench.make_root(tmp_path, [("g4.x4", conf, "rounds", 4)])
    jax.clear_caches()
    out = run.run_cell(root, "g4.x4", 7, 0.5, True, require_tpu=False)
    assert out["correct"] and out["attempted"] == 4
    assert not set(out["metrics"]) & {f"{name}.x4" for name in EXCHANGE}
