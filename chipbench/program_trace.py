"""Read what the program itself writes into a profiler trace.

Two things the trace reduction in ``xplane.py`` does not see:

* each device operation's ``tf_op`` stat: the HLO ``op_name`` of the
  instruction, which carries the ``jax.named_scope`` of the round's phase
  (``ne_select``, ``ne_one_hop``, ``ne_sync``, ``ne_two_hop``).  It is a
  stat of the event's *metadata*, which ``jax.profiler.ProfileData`` does
  not expose, so the ``.xplane.pb`` is read here with ``google.protobuf``
  from a descriptor of the XSpace messages (no TensorFlow);
* the program's own host spans: ``repro.<name>`` ``TraceAnnotation``
  events (``repro.obs`` spans and ``repro.compile`` marks).

An op's phase is the innermost ``ne_*`` component of its ``tf_op``; an op
with none is ``other``.  Times are nanoseconds on the trace's one clock,
computed as ``ProfileData`` computes them, so the op intervals equal
``xplane.load``'s.  The readers below take the per-layer readers' context,
whose ``trace`` is ``xplane.load``'s reduction of the run's trace; the file
it came from is the one under ``run.py``'s trace directories whose device
ops have the same intervals.  They return None where the trace holds
nothing of the kind to read: no such file, no traced round, no phase
scope, or no ``repro.*`` span (a program without the instrumentation).
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from pathlib import Path

import xplane

PHASES = ("ne_select", "ne_one_hop", "ne_sync", "ne_two_hop")
OTHER = "other"
PREFIX = "repro."
_DEVICE = re.compile(r"/device:TPU:\d+")
# where run.py's profiler sessions write (one directory per cell)
TRACES = str(Path(__file__).resolve().parent.parent / ".chipbench" / "trace"
             / "*" / "plugins" / "profile" / "*" / "*.xplane.pb")


def _messages():
    """The XSpace message class, built from a descriptor of the fields of
    ``tsl/profiler/protobuf/xplane.proto`` read here (field numbers as
    there; the parser skips the rest)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench.xplane",
        syntax="proto3")

    def message(name, *fields, nested=(), oneof=None):
        m = fdp.message_type.add(name=name)
        for sub in nested:
            m.nested_type.add().CopyFrom(sub)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if type_name:
                f.type_name = type_name
            if oneof and number > 1:
                f.oneof_index = 0
        return m

    opt, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, dbl = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    s, msg = F.TYPE_STRING, F.TYPE_MESSAGE
    p = ".chipbench.xplane."

    def entry(name, value_type):
        e = descriptor_pb2.DescriptorProto(name=name)
        e.options.map_entry = True
        e.field.add(name="key", number=1, type=i64, label=opt)
        e.field.add(name="value", number=2, type=msg, label=opt,
                    type_name=p + value_type)
        return e

    message("XStat", ("metadata_id", 1, i64, opt, None),
            ("double_value", 2, dbl, opt, None),
            ("uint64_value", 3, u64, opt, None),
            ("int64_value", 4, i64, opt, None),
            ("str_value", 5, s, opt, None), ("ref_value", 7, u64, opt, None),
            oneof="value")
    message("XEvent", ("metadata_id", 1, i64, opt, None),
            ("offset_ps", 2, i64, opt, None),
            ("duration_ps", 3, i64, opt, None),
            ("stats", 4, msg, rep, p + "XStat"))
    message("XLine", ("name", 2, s, opt, None),
            ("timestamp_ns", 3, i64, opt, None),
            ("events", 4, msg, rep, p + "XEvent"))
    message("XEventMetadata", ("name", 2, s, opt, None),
            ("stats", 5, msg, rep, p + "XStat"))
    message("XStatMetadata", ("name", 2, s, opt, None))
    message("XPlane", ("name", 2, s, opt, None),
            ("lines", 3, msg, rep, p + "XLine"),
            ("event_metadata", 4, msg, rep,
             p + "XPlane.EventMetadataEntry"),
            ("stat_metadata", 5, msg, rep, p + "XPlane.StatMetadataEntry"),
            nested=(entry("EventMetadataEntry", "XEventMetadata"),
                    entry("StatMetadataEntry", "XStatMetadata")))
    message("XSpace", ("planes", 1, msg, rep, p + "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.xplane.XSpace"))


def _interval(line, event):
    # as ProfileData: whole nanoseconds from the line's timestamp
    start = line.timestamp_ns + event.offset_ps // 1000
    return start, start + event.duration_ps // 1000


def _stats(plane, stats) -> dict:
    """Stat name → value; a ``ref_value`` names a stat metadata entry."""
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        value = getattr(st, kind) if kind else None
        if kind == "ref_value":
            value = plane.stat_metadata[value].name
        out[plane.stat_metadata[st.metadata_id].name] = value
    return out


def phase(tf_op: str) -> str:
    """The innermost ``ne_*`` component of an op's ``tf_op``."""
    scopes = [c for c in tf_op.split("/") if c in PHASES]
    return scopes[-1] if scopes else OTHER


@dataclasses.dataclass
class ProgramTrace:
    devices: list     # per device: sorted [(op name, start_ns, end_ns, tf_op)]
    spans: list       # sorted [(name less "repro.", start_ns, end_ns, stats)]

    def spans_in(self, name: str, window) -> list:
        a, b = window
        return [(s, e) for n, s, e, _ in self.spans
                if n == name and s >= a and e <= b]

    def phase_ns(self, window) -> dict:
        """Device self time per phase inside ``window``, mean over the
        devices (``xplane``'s self times: a loop's own time less its
        body's ops)."""
        a, b = window
        out: dict = {}
        for ops in self.devices:
            clipped = xplane._clip([(phase(t), s, e) for _, s, e, t in ops],
                                   a, b)
            for name, t in xplane._self_times(clipped):
                out[name] = out.get(name, 0.0) + t
        return {k: v / max(len(self.devices), 1) for k, v in out.items()}


@functools.lru_cache(maxsize=1)
def load(path: str) -> ProgramTrace:
    space = _messages()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, spans = [], []
    for plane in space.planes:
        if _DEVICE.fullmatch(plane.name):
            meta = {k: (xplane.op_name(em.name),
                        _stats(plane, em.stats).get("tf_op", ""))
                    for k, em in plane.event_metadata.items()}
            ops = []
            for line in plane.lines:
                if line.name == xplane.OPS_LINE:
                    for e in line.events:
                        name, tf_op = meta[e.metadata_id]
                        ops.append((name, *_interval(line, e), tf_op))
            devices.append(sorted(ops, key=lambda o: o[1]))
        elif plane.name.startswith("/host:"):
            ours = {k: em.name[len(PREFIX):]
                    for k, em in plane.event_metadata.items()
                    if em.name.startswith(PREFIX)}
            if not ours:
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.metadata_id in ours:
                        spans.append((ours[e.metadata_id],
                                      *_interval(line, e),
                                      _stats(plane, e.stats)))
    return ProgramTrace(devices, sorted(spans, key=lambda s: s[1]))


# -- readers (one line each under layers/) ---------------------------------

def _intervals(devices) -> list:
    return sorted(sorted((o[1], o[2]) for o in ops) for ops in devices)


_found: list = []                     # [(trace, path)] of the last lookup


def trace_path(ctx) -> str | None:
    """The ``.xplane.pb`` that the context's ``trace`` was read from: the
    newest file under ``TRACES`` whose device op intervals are the
    trace's, or None."""
    tr = ctx["trace"]
    if _found and _found[0][0] is tr:
        return _found[0][1]
    want, path = _intervals(tr.devices), None
    for p in sorted(glob.glob(TRACES), key=os.path.getmtime, reverse=True):
        if _intervals(load(p).devices) == want:
            path = p
            break
    _found[:] = [(tr, path)]
    return path


def _program_trace(ctx):
    path = trace_path(ctx)
    return None if path is None else load(path)


def _rounds(ctx):
    """The traced rounds' window and count (``xplane.rounds``) and the
    program trace, or None."""
    r = xplane.rounds(ctx["trace"])
    pt = None if r is None else _program_trace(ctx)
    return None if pt is None else (*r, pt)


def _phase_ms(ctx, name):
    got = _rounds(ctx)
    if got is None:
        return None
    win, n, pt = got
    ns = pt.phase_ns(win)
    if not set(ns) & set(PHASES):
        return None                   # a round program without the scopes
    return ns.get(name, 0.0) / n / 1e6


def select_ms(ctx):
    """Device ms per traced round in ops under ``ne_select``."""
    return _phase_ms(ctx, "ne_select")


def one_hop_ms(ctx):
    """Device ms per traced round in ops under ``ne_one_hop``."""
    return _phase_ms(ctx, "ne_one_hop")


def sync_ms(ctx):
    """Device ms per traced round in ops under ``ne_sync``: the local (P,)
    and D_rest deltas and the collectives, both syncs of a round."""
    return _phase_ms(ctx, "ne_sync")


def two_hop_ms(ctx):
    """Device ms per traced round in ops under ``ne_two_hop`` (the scan's
    ``while`` op's own time included), less its nested ``ne_sync``."""
    return _phase_ms(ctx, "ne_two_hop")


def other_ms(ctx):
    """Device ms per traced round in ops with no phase scope."""
    return _phase_ms(ctx, OTHER)


def host_ms(ctx):
    """Host ms per traced round on the round's critical path, from the
    program's spans: each ``round`` span less its ``round_wait`` child
    (dispatch, the ``rounds`` read, bookkeeping), plus ``done_read``."""
    got = _rounds(ctx)
    if got is None:
        return None
    win, n, pt = got
    rounds = pt.spans_in("round", win)
    if not rounds:
        return None                   # a driver without the spans
    wait = pt.spans_in("round_wait", win)
    done = pt.spans_in("done_read", win)
    total = (sum(e - s for s, e in rounds) - sum(e - s for s, e in wait)
             + sum(e - s for s, e in done))
    return total / n / 1e6


def compiles(ctx):
    """``repro.compile`` marks (JAX compile events) inside the traced
    window: from the first to the last of the benchmark's spans."""
    tr = ctx["trace"]
    win = tr.span_window(*{s for s, _, _ in tr.spans})
    pt = None if win is None else _program_trace(ctx)
    if pt is None or not pt.spans:
        return None                   # a program without the marks
    return float(len(pt.spans_in("compile", win)))
