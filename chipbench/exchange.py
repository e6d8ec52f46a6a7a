"""The cross-device exchange of an SPMD round, read from a profiler trace.

The program puts each collective call of its round (the replica-set,
D_rest and count all-reduces of both syncs, and the two-hop histogram
gather) under a ``jax.named_scope("ne_exchange")``, nested in the
``ne_sync`` or ``ne_two_hop`` phase it belongs to, and gives each
``repro.round`` span the bytes its sync sends as the argument
``sync_payload_bytes``.  The readers below take the per-layer readers'
context and return None where the trace holds none of that: one device,
where the collectives compile away, or a program without the scope or the
argument.
"""
from __future__ import annotations

import program_trace
import xplane

SCOPE = "ne_exchange"


def exchange_ms(ctx):
    """Device self ms per traced round in ops whose ``tf_op`` has an
    ``ne_exchange`` component, mean over the devices; None where no op in
    the traced rounds has it."""
    got = program_trace._rounds(ctx)
    if got is None:
        return None
    (a, b), n, pt = got
    total, found = 0.0, False
    for ops in pt.devices:
        ours = [(SCOPE in tf_op.split("/"), s, e) for _, s, e, tf_op in ops]
        for inside, t in xplane._self_times(xplane._clip(ours, a, b)):
            if inside:
                total += t
                found = True
    if not found:
        return None
    return total / len(pt.devices) / n / 1e6


def exchange_min_bytes(n: int, p: int, d: int) -> float:
    """The least bytes each of ``d`` devices must send in one round with
    two-hop allocation, whatever implements the sync: each of the two
    syncs all-reduces B = N·P/8 bytes of replica delta as bits, 4N of
    D_rest and 4P of counts, and an all-reduce of B bytes over d devices
    sends at least 2(d-1)/d·B from each; the quota split gathers the
    other devices' (P,) int32 histograms."""
    b = n * p / 8 + 4 * n + 4 * p
    return 2 * (2 * (d - 1) / d * b) + (d - 1) * p * 4


def exchange_roofline_pct(ctx):
    """The least bytes of the exchange (``exchange_min_bytes``) at the
    chip's ICI bandwidth, as a share of ``exchange_ms``, in %."""
    ms = exchange_ms(ctx)
    if not ms:
        return None
    least_s = (exchange_min_bytes(ctx["n"], ctx["p"], ctx["d"])
               / (ctx["peak"]["ici_bits_per_s"] / 8))
    return 100.0 * least_s / (ms / 1e3)


def exchange_mb(ctx):
    """MB per traced round that the program says its sync sends: the
    ``sync_payload_bytes`` argument of the traced ``round`` spans; None
    where they lack it."""
    got = program_trace._rounds(ctx)
    if got is None:
        return None
    (a, b), n, pt = got
    sent = [stats.get("sync_payload_bytes") for name, s, e, stats in pt.spans
            if name == "round" and s >= a and e <= b]
    if not sent or None in sent:
        return None
    return sum(float(x) for x in sent) / n / 1e6
