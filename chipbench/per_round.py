"""Per-round readings of the traced rounds, shared by the ``.jobs`` and
``.rounds`` readers under ``layers/``: each takes the reader's context and
returns a number, or None where no round or no device was traced."""
import roofline
import xplane


def device_ms(ctx):
    """Device-busy milliseconds per round, averaged over the devices."""
    r = xplane.rounds(ctx["trace"])
    if r is None:
        return None
    win, n = r
    return ctx["trace"].busy_ns(win) / n / 1e6


def idle_pct(ctx):
    """The share of the rounds' window with no device op, in %."""
    r = xplane.rounds(ctx["trace"])
    if r is None:
        return None
    (a, b), _ = r
    return 100.0 * (1.0 - ctx["trace"].busy_ns((a, b)) / (b - a))


def hbm_roofline_pct(ctx):
    """The least bytes a round must move (``roofline.py``) at the peak HBM
    bandwidth, as a share of the device-busy time per round, in %."""
    ms = device_ms(ctx)
    if not ms:
        return None
    least_s = (roofline.round_min_bytes(ctx["n"], ctx["m"], ctx["p"],
                                        ctx["d"])
               / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
