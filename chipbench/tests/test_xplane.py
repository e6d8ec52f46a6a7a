"""The trace reduction, checked on a small trace recorded on a TPU v5e
(``record_trace.py``: Graph500 scale 12, one chip, four traced rounds)."""
from pathlib import Path

import numpy as np
import pytest

import xplane

TRACE = (Path(__file__).resolve().parents[1] / "testdata"
         / "rounds_s12.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return xplane.load(str(TRACE))


def test_spans_and_devices(tr):
    assert len(tr.devices) == 1 and len(tr.devices[0]) > 0
    assert tr.count("step") == 4
    names = {s for s, _, _ in tr.spans}
    assert names <= {"done", "step"}
    for (_, a0, b0), (_, a1, _) in zip(tr.spans, tr.spans[1:]):
        assert a0 <= b0 <= a1      # one after another, never nested


def test_busy_equals_a_timeline_count(tr):
    """The interval union against a brute-force 100 ns timeline."""
    win, n = xplane.rounds(tr)
    a, b = win
    step = 100.0
    t = np.zeros(int((b - a) / step) + 1, bool)
    for _, s, e in tr.devices[0]:
        lo, hi = max(s, a), min(e, b)
        if hi > lo:
            t[int((lo - a) / step): int(np.ceil((hi - a) / step))] = True
    brute = t.sum() * step
    busy = tr.busy_ns(win)
    assert 0 < busy <= b - a
    assert abs(busy - brute) <= 2 * step * (len(tr.devices[0]) + 1)


def test_idle_gaps_and_busy_fill_the_window(tr):
    win, _ = xplane.rounds(tr)
    gaps = tr.idle_gaps(win)
    assert set(gaps) <= {"done", "step", "outside_spans"}
    assert sum(gaps.values()) + tr.busy_ns(win) == pytest.approx(
        win[1] - win[0], rel=1e-9)


def test_op_times_cover_busy(tr):
    win, _ = xplane.rounds(tr)
    ops = tr.op_ns(win)
    assert sum(ops.values()) >= tr.busy_ns(win) * (1 - 1e-9)
    assert not tr.op_ns(win, xplane.COLLECTIVE)   # one chip: no collective


def test_readers_on_the_trace(tr):
    import importlib.util

    layers = Path(__file__).resolve().parents[1] / "layers"

    def read(name, ctx):
        spec = importlib.util.spec_from_file_location(name,
                                                      layers / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)

    ctx = dict(trace=tr, spans=[], kind="rounds", n=1 << 12, m=48_655,
               p=16, d=1, peak={"hbm_bytes_per_s": 819e9})
    win, n = xplane.rounds(tr)
    assert read("round_device_ms.rounds", ctx) == pytest.approx(
        tr.busy_ns(win) / n / 1e6)
    idle = read("round_idle.rounds", ctx)
    assert 0 <= idle < 100
    roof = read("round_hbm_roofline.rounds", ctx)
    assert 0 < roof < 100
    for name in ("round_device_ms", "round_idle", "round_hbm_roofline"):
        assert read(f"{name}.jobs", ctx) == read(f"{name}.rounds", ctx)
