"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device operations come from the ``XLA Ops`` line of each ``/device:TPU:<i>``
plane; host spans are the benchmark's own ``chipbench.<name>``
``TraceAnnotation`` events on the host plane.  Both are on the trace's one
clock, in nanoseconds.  From them:

* busy time: the union of a device's operation intervals inside a window;
* idle gaps: the rest of the window, each gap named by the innermost host
  span that covers its midpoint (what the host was doing meanwhile);
* self time per operation (HLO instruction) name, and in collectives
  (all-reduce, all-gather, ...).

Every per-device number is averaged over the devices traced.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


@dataclasses.dataclass
class Trace:
    devices: list          # per device: sorted [(name, start_ns, end_ns)]
    spans: list            # sorted [(name, start_ns, end_ns)], prefix cut

    def __post_init__(self):
        self._starts = [a for _, a, _ in self.spans]

    def span_window(self, *names) -> tuple[float, float] | None:
        """From the first start to the last end of the named spans."""
        hit = [(a, b) for s, a, b in self.spans if s in names]
        if not hit:
            return None
        return min(a for a, _ in hit), max(b for _, b in hit)

    def count(self, name: str) -> int:
        return sum(1 for s, _, _ in self.spans if s == name)

    def busy_ns(self, window) -> float:
        a, b = window
        return _mean(_union_len(_clip(ops, a, b)) for ops in self.devices)

    def op_ns(self, window, pattern=None) -> dict:
        """Device self time per operation name inside ``window``: an op's
        time less that of the ops nested in it (a loop's body ops)."""
        a, b = window
        out: dict = {}
        for ops in self.devices:
            for name, t in _self_times(_clip(ops, a, b)):
                if pattern is None or pattern.search(name):
                    out[name] = out.get(name, 0.0) + t
        return {k: v / max(len(self.devices), 1) for k, v in out.items()}

    def idle_gaps(self, window) -> dict:
        """Idle device time inside ``window`` by the host span around it."""
        a, b = window
        out: dict = {}
        for ops in self.devices:
            t = a
            for s, e in _merged(_clip(ops, a, b)) + [(b, b)]:
                if s > t:
                    mid = (s + t) / 2
                    name = self.host_span_at(mid)
                    out[name] = out.get(name, 0.0) + (s - t)
                t = max(t, e)
        return {k: v / max(len(self.devices), 1) for k, v in out.items()}

    def host_span_at(self, t: float) -> str:
        # the benchmark's spans follow one another and do not nest
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self.spans[i][2]:
            return self.spans[i][0]
        return "outside_spans"


def rounds(tr: Trace):
    """The traced rounds: their window (from the first ``done`` read or
    ``step`` to the last) and their count; None where no round or no
    device was traced."""
    win = tr.span_window("done", "step")
    n = tr.count("step")
    return (win, n) if tr.devices and win and n else None


def _mean(xs) -> float:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else 0.0


def _clip(ops, a, b):
    return [(n, max(s, a), min(e, b)) for n, s, e in ops if e > a and s < b]


def _merged(ops) -> list:
    out: list = []
    for _, s, e in sorted(ops, key=lambda o: o[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _self_times(ops):
    """(name, duration less nested ops') for ops sorted by start."""
    out, stack = [], []             # stack: [name, end, self time]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    out.extend(tuple(x[::2]) for x in stack)
    return out


def _union_len(ops) -> float:
    return sum(e - s for s, e in _merged(ops))


def op_name(hlo: str) -> str:
    """An op event's name is its HLO text; keep the instruction's name and
    its result's shape: ``fusion.142 s32[262144]``."""
    name, _, rest = hlo.partition(" = ")
    shape = re.match(r"[^{ ]*", rest).group(0)
    return f"{name.lstrip('%')} {shape}".strip()


def find(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = [(op_name(e.name), e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(sorted(ops, key=lambda o: o[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns, e.end_ns))
    return Trace(devices, sorted(spans, key=lambda s: s[1]))
