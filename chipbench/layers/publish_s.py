"""Finalize + artifact layer (core/epilogue, runtime/finalize,
runtime/artifact): host seconds in ``finalize()`` and ``save_artifact()``,
per traced job.  Jobs cells."""


def read(ctx):
    spans = ctx["spans"]
    jobs = sum(1 for name, _, _ in spans if name == "save")
    total = sum(b - a for name, a, b in spans
                if name in ("finalize", "save"))
    return total / jobs if jobs else None
