"""``program_trace.py`` on traces recorded on a TPU v5e (``record_trace.py``:
Graph500 scale 12, one chip, four traced rounds): ``rounds_s12`` from a
round program without phase scopes or program spans, ``rounds_s12_scoped``
from one with them."""
import importlib.util
from pathlib import Path

import pytest

import program_trace
import xplane

DATA = Path(__file__).resolve().parents[1] / "testdata"
LAYERS = Path(__file__).resolve().parents[1] / "layers"
PLAIN = str(DATA / "rounds_s12.xplane.pb")
SCOPED = str(DATA / "rounds_s12_scoped.xplane.pb")
NEW = ("round_select_ms", "round_one_hop_ms", "round_sync_ms",
       "round_two_hop_ms", "round_host_ms")


def read(name, ctx):
    spec = importlib.util.spec_from_file_location(name, LAYERS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@pytest.fixture(autouse=True)
def traces_here(monkeypatch):
    """The readers look for the trace's file among the recorded ones."""
    monkeypatch.setattr(program_trace, "TRACES", str(DATA / "*.xplane.pb"))


def context(path):
    return dict(trace=xplane.load(path), spans=[],
                kind="rounds", n=1 << 12, m=48_655, p=16, d=1,
                peak={"hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("path", [PLAIN, SCOPED], ids=["plain", "scoped"])
def test_ops_equal_the_trace_reduction(path):
    """The same op intervals as ``xplane.load``: count, order, total ns."""
    ops = program_trace.load(path).devices
    want = xplane.load(path).devices
    assert [[o[:3] for o in d] for d in ops] == want
    assert len(ops[0]) == len(want[0]) > 0
    assert (sum(e - s for _, s, e, _ in ops[0])
            == sum(e - s for _, s, e in want[0]))


def test_tf_op_is_the_hlo_op_name():
    ops = program_trace.load(PLAIN).devices[0]
    tf_op = {name: t for name, _, _, t in ops}
    assert tf_op["compare_reduce_fusion.19 pred[]"] == (
        "jit(spmd_round_step)/while/body/closed_call/reduce_or:")


@pytest.mark.parametrize("tf_op,phase", [
    ("jit(spmd_round_step)/ne_select/while/body/closed_call/top_k:",
     "ne_select"),
    ("jit(spmd_round_step)/ne_two_hop/ne_sync/scatter-add:", "ne_sync"),
    ("jit(spmd_round_step)/ne_two_hop/while", "ne_two_hop"),
    ("jit(spmd_round_step)/ne_syncx/add:", "other"),
    ("", "other")])
def test_phase_is_the_innermost_scope(tf_op, phase):
    assert program_trace.phase(tf_op) == phase


@pytest.mark.parametrize("path", [PLAIN, SCOPED], ids=["plain", "scoped"])
def test_trace_path_finds_the_file_the_trace_came_from(path):
    assert program_trace.trace_path(context(path)) == path


def test_readers_read_nothing_without_the_file(monkeypatch, tmp_path):
    """A trace whose file is not under ``TRACES`` gives no reading."""
    monkeypatch.setattr(program_trace, "TRACES", str(tmp_path / "*.pb"))
    ctx = context(SCOPED)
    assert program_trace.trace_path(ctx) is None
    for name in NEW:
        assert read(f"{name}.rounds", ctx) is None
    assert read("window_compiles.jobs", ctx) is None


def test_readers_read_nothing_without_the_instrumentation():
    """A round program without the scopes and spans (the one ``rounds_s12``
    was recorded from) gives no reading, and no error."""
    ctx = context(PLAIN)
    for name in NEW:
        for cell in ("jobs", "rounds"):
            assert read(f"{name}.{cell}", ctx) is None
    assert read("window_compiles.jobs", ctx) is None


def test_readers_on_the_scoped_trace():
    ctx = context(SCOPED)
    device = read("round_device_ms.rounds", ctx)
    phases = [read(f"{name}.rounds", ctx) for name in NEW[:4]]
    assert all(ms > 0 for ms in phases)
    other = program_trace.other_ms(ctx)
    assert sum(phases) + other == pytest.approx(device, rel=0.01)
    win, n = xplane.rounds(ctx["trace"])
    host = read("round_host_ms.rounds", ctx)
    assert 0 < host < (win[1] - win[0]) / n / 1e6
    assert read("window_compiles.jobs", ctx) >= 0
    for name in NEW:
        assert read(f"{name}.jobs", ctx) == read(f"{name}.rounds", ctx)
