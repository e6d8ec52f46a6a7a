"""Ingest layer (io/stream, runtime/cluster): host seconds in the
``PartitionDriver`` constructor, per traced job.  Jobs cells: in a rounds
cell ingest is set-up and no span of it is in the window."""


def read(ctx):
    spans = [b - a for name, a, b in ctx["spans"] if name == "ingest"]
    return sum(spans) / len(spans) if spans else None
