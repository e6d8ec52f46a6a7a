"""The least work of one NE round, whatever implements it.

Per device, a round must read each of its edges' endpoints once (2 x int32)
and read and write its ``edge_part`` entries (int32); every device holds
the replicated state, whose replica map it reads and writes once at one
byte per (vertex, partition) and whose ``degree_rest`` it reads and writes
(int32).  Selection, claims and sync all touch these same arrays, so this
is a floor on the bytes moved from HBM, not a model of the program.
"""


def round_min_bytes(n: int, m: int, p: int, d: int) -> float:
    edges_per_device = m / d
    return (edges_per_device * (2 * 4 + 2 * 4)
            + 2 * n * p
            + 2 * 4 * n)
